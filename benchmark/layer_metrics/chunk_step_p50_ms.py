"""Model step (models/generation.py): one decode step as the engine clocks
the chunk itself, median of ring ``chunk_ms / chunk`` over EVERY window
boundary that ran a chunk (``chunk_ms`` is upload, dispatch, wait and fetch
around ``slot_decode_chunk``; ``decode_step_p50_ms`` can only use the
boundaries that admitted nothing). A ring without the field (a program older
than the split) gives nothing."""

from measure import percentile


def read(run):
    steps = [s["chunk_ms"] / s["chunk"] for s in run.window_steps()
             if s["chunk"] > 0 and s.get("chunk_ms") is not None]
    return (percentile(steps, 50), len(steps)) if steps else None
