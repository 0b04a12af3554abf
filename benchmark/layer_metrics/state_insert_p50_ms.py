"""Arena (SlotDecodeState): what writing an admitted request's lane state
costs the engine's thread, median of the ``state_insert`` spans of the traced
span's admissions (``runtime.slot_admit``: the dispatch that puts the
prefill's convolution state into the lane's slice, beside the page insert).
The span is the program's ``tpusc.state_insert`` annotation in the capture
(``utils/tracing.host_span``), on the profiler's clock.

A program without the span, or a capture that cannot be found, gives nothing;
a rehearsal shows the admissions the ring says the span held, as a count."""

import capture_scopes
from measure import percentile


def read(run):
    if not run.trace_wall:
        return None
    if run.device.get("platform") != "tpu":
        if "layer_types" not in run.program_config:
            return None
        lo, hi = run.trace_wall
        held = sum(s["admitted"] for s in run.steps if lo <= s["t_wall"] <= hi)
        return 0.0, max(1, held)
    capture = capture_scopes.capture_of(run)
    spans = (capture or {}).get("host", {}).get("tpusc.state_insert")
    if not spans:
        return None
    return percentile([s * 1e3 for s in spans], 50), len(spans)
