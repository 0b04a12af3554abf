"""Expert layer (ops/moe.py): assignments a layer a decode step that landed
on an expert this chip holds, mean over the window's boundaries that ran a
decode chunk (ring ``expert_rows_local``, itself the chunk's mean over its
steps and layers, counted inside the decode program). Of a step's ``active x
top_k`` assignments a quarter share expects a quarter; the rows the expert
kernel multiplies, and with ``experts_hit`` the weights it reads, follow it.
A program whose ring has no such field gives nothing."""


def read(run):
    rows = [s["expert_rows_local"] for s in run.window_steps()
            if s["chunk"] > 0 and s.get("experts_hit")
            and "expert_rows_local" in s]
    return (sum(rows) / len(rows), len(rows)) if rows else None
