"""Model step (models/generation.py): one decode step, median of ring
``step_ms / chunk`` over the window's boundaries with ``admitted = 0`` (a
boundary's ``step_ms`` is admission + prefill + chunk, so only those time a
decode chunk alone)."""

from measure import percentile


def read(run):
    steps = [s["step_ms"] / s["chunk"] for s in run.window_steps()
             if s["chunk"] > 0 and s["admitted"] == 0]
    return (percentile(steps, 50), len(steps)) if steps else None
