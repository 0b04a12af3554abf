"""Arena (SlotDecodeState): the device time of writing an admitted request's
prefill rows into its lane's pages: device wall of the traced span's
``_paged_insert_jit`` executions (the capture's ``XLA Modules`` line,
``capture_programs.py``) over their count, one an admission. No host clock
sees it: the insert is dispatched and not waited for, so its time hides in
the NEXT chunk's ``chunk_ms`` (the chunk's launch path runs meanwhile).

A span that held no admission, or a capture that cannot be found, gives
nothing; a rehearsal shows the admissions the ring says the span held, as a
count."""

import capture_programs


def read(run):
    if not run.trace_wall:
        return None
    if not capture_programs.on_chip(run):
        lo, hi = run.trace_wall
        held = sum(s["admitted"] for s in run.steps if lo <= s["t_wall"] <= hi)
        return (0.0, held) if held else None
    cap = capture_programs.capture_of(run)
    if cap is None:
        return None
    table = cap["programs"]
    wall = [end - start for name, start, end
            in zip(table["name"], table["start"], table["end"])
            if capture_programs.INSERT in name]
    return (sum(wall) / len(wall) / 1e6, len(wall)) if wall else None
