"""Engine (runtime/batcher.py): what every live lane waits when a boundary
admits, median of ring ``prefill_ms`` over the window's boundaries with
``admitted > 0`` (the admission prefills run before the decode chunk, on the
engine's one thread). A ring without the field gives nothing."""

from measure import percentile


def read(run):
    stalls = [s["prefill_ms"] for s in run.window_steps()
              if s["admitted"] > 0 and s.get("prefill_ms") is not None]
    return (percentile(stalls, 50), len(stalls)) if stalls else None
