"""Expert layer (ops/moe.py): distinct experts a layer a decode step routed
rows to, mean over the window's boundaries that ran a decode chunk (ring
``experts_hit``, itself the chunk's mean over its steps and layers, computed
inside the decode program and fetched with its tokens). The bytes a step
must read follow it: an expert no row chose is not read. A program whose
ring has no such field gives nothing."""


def read(run):
    hit = [s["experts_hit"] for s in run.window_steps()
           if s["chunk"] > 0 and s.get("experts_hit")]
    return (sum(hit) / len(hit), len(hit)) if hit else None
