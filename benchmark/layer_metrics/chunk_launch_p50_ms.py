"""Engine (runtime/batcher.py): a decode chunk's LAUNCH PATH on the engine's
thread, median of ring ``launch_ms`` over the window's boundaries that ran a
chunk: from ``slot_decode_chunk``'s entry until the chunk's program call has
returned its futures (residency lookup, the key programs, the conversion and
upload of the mirrors and block tables, the call). It is the part of
``chunk_ms`` the chip stands still for unless an admission's programs still
run; ``chunk_ms - launch_ms`` is the wait for the device plus the fetch, and
``boundary_host_p50_ms`` the time between two ``chunk_ms``. The profiler's
``tpusc.chunk_launch`` annotation covers the same stretch. A ring without the
field (a program older than it) gives nothing."""

from measure import percentile


def read(run):
    launch = [s["launch_ms"] for s in run.window_steps()
              if s["chunk"] > 0 and s.get("launch_ms") is not None]
    return (percentile(launch, 50), len(launch)) if launch else None
