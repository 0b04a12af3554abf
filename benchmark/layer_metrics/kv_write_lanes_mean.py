"""Model step (models/generation.py): the lanes whose KV rows a decode step
wrote into the arena, mean over the window's boundaries that ran a decode
chunk (ring ``write_lanes`` where ``chunk > 0``): the chunk's live lanes
rounded up to whole trips of the write's loop (four lanes a trip), worked out
on the engine thread from the ``active`` mirror the chunk was dispatched
with. The row scatter is sequential in its rows, so a step's write costs what
this reads, not the lanes the engine was built with. A program whose ring has no such
field (it writes every lane's rows) gives nothing."""


def read(run):
    lanes = [s["write_lanes"] for s in run.window_steps()
             if s["chunk"] > 0 and "write_lanes" in s]
    return (sum(lanes) / len(lanes), len(lanes)) if lanes else None
