"""Engine (runtime/batcher.py): program executions a decode chunk costs,
mean over the traced span's decode-only back-to-back boundaries of the
executions on the device from one decode chunk's end to the next one's end,
the chunk included (``capture_programs.py``, the capture's ``XLA Modules``
line). Each execution is a launch of its own from the engine's thread: the
chunk, and whatever tiny programs the host runs to make its arguments (the
run prints their names). 1.0 is a chunk that needs nothing launched before
it.

A capture that cannot be found, or a span without two chunks back to back,
gives nothing; a rehearsal shows the boundaries the ring says the span held,
as a count."""

import statistics

import capture_programs


def read(run):
    return capture_programs.over_counted(
        run, lambda chunks: statistics.fmean(c["launches"] for c in chunks))
