"""Model step (models/generation.py): the device's idle time INSIDE the decode
chunk's program a decode step: over the traced span's executions of
``_paged_decode_chunk_jit`` (the capture's ``XLA Modules`` line), device wall
minus busy (the union of the ``XLA Ops`` inside the execution, wrappers left
out), summed, over the decode steps the ring says the span held
(``capture_scopes.decode_steps``). It is what "fewer, larger operations a
step" could win back; the idle time BETWEEN programs is ``chunk_gap_p50_ms``.

A capture that cannot be found or holds no such execution gives nothing; a
rehearsal shows the steps as a count."""

import capture_programs
import capture_scopes


def read(run):
    steps = capture_scopes.decode_steps(run)
    if steps <= 0:
        return None
    if not capture_programs.on_chip(run):
        return 0.0, max(1, round(steps))
    cap = capture_programs.capture_of(run)
    if cap is None:
        return None
    table = cap["programs"]
    idle = [ns for name, ns in zip(table["name"], table["idle"])
            if capture_programs.DECODE in name]
    if not idle:
        return None
    return sum(idle) / 1e6 / steps, max(1, round(steps))
