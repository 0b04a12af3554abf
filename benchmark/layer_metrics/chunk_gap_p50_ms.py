"""Engine (runtime/batcher.py): how long the device stands still between two
decode chunks, median over the traced span's decode-only back-to-back
boundaries of the device's idle time from one chunk's last operation to the
next one's first (``capture_programs.py``: the capture's ``XLA Modules`` and
``XLA Ops`` lines; a boundary counts when no prefill, insert, prefill chunk or
speculation round ran between the two chunks and the engine did not wait for
a request before it). It is the sum of the last chunk's fetch return, the
boundary's host work and this chunk's launch path (``chunk_launch_p50_ms``):
the run prints both sides.

A capture that cannot be found, or a span without two chunks back to back,
gives nothing; a rehearsal shows the boundaries the ring says the span held,
as a count."""

import capture_programs
from measure import percentile


def read(run):
    return capture_programs.over_counted(
        run, lambda chunks: percentile([c["gap_ns"] / 1e6 for c in chunks], 50))
