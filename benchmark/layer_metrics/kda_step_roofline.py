"""Model step (models/generation.py): the delta rule's one-token step's share
of its roofline over the traced span, where the decay is a channel's. For
every step call the span held (``kernel_costs_kda.step_calls``: a ring
boundary that ran a chunk stands for ``chunk x linear layers`` calls, weighted
by its share inside the span, at the ring's live lanes) the least time the
chip could take for the LIVE lanes' bytes (each live lane's state read once
and written once, its operands, its decays and its output;
``kernel_costs_kda.step`` over the HBM peak, ``peaks.json``), summed, over the
device time of the operations of the decode chunk's program under
``layer/kda/step``. ``state_write_lanes_mean`` beside it says how many lanes'
slices the program touched.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_kda as costs


def read(run):
    calls = costs.step_calls(run)
    if not calls:
        return None
    expected = sum(c for _lanes, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    capture = capture_scopes.capture_of(run)
    if capture is None or capture["device"] is None:
        return None
    seconds, events = capture_scopes.scope_seconds(
        capture["ops"], capture_scopes.DECODE_PROGRAM, "layer/kda/step")
    if not events or seconds <= 0:
        return None
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    least = sum(
        count * costs.roofline(costs.step(
            lanes, mc["linear_heads"], mc["linear_key_dim"],
            mc["linear_value_dim"]), peak)["seconds"]
        for lanes, count in calls)
    print(f"kda step roofline: {expected:.0f} calls expected from the ring at "
          f"{sum(ln * c for ln, c in calls) / max(expected, 1e-9):.2f} live "
          f"lanes a call; least {least * 1e3:.2f} ms against "
          f"{seconds * 1e3:.2f} ms measured over {events} operations",
          flush=True)
    return 100.0 * least / seconds, max(1, round(expected))
