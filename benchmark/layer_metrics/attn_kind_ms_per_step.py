"""Model step (models/generation.py): device milliseconds a decode step spends
under ``layer/attn/window``, all the window layers together, with the same
under ``layer/attn/global`` printed beside it: what the window layers' 72
query heads cost beside the global layers' 48 in ``laguna-repoctx-steady``
(6 layers against 3). A kind's scope holds the decode call over the arena
where it lies, the output gate (``.../gate``) and the heads' product with
``wo``, with the waits for the layer's own weights
(``capture_scopes.consumer_scopes`` gives a fetch its user's path); the
projection before the rows are written lies under ``layer/attn`` alone, in
every model. The window layers' value is reported; the pair is in the printed
line, a layer each.

A model without heads a layer, a program without the scopes, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_heads as costs


def read(run):
    mc = run.program_config
    if not costs.has_heads_a_layer(mc):
        return None
    window = capture_scopes.decode_scope_ms(run, "layer/attn/window", 1)
    if window is None or run.device.get("platform") != "tpu":
        return window
    glob = capture_scopes.decode_scope_ms(run, "layer/attn/global", 1)
    gate = capture_scopes.decode_scope_ms(run, "gate", 1)
    n_w = len(costs.kind_heads(mc, costs.SLIDING))
    n_g = len(costs.kind_heads(mc, costs.FULL))
    print(f"attn kind ms a step: layer/attn/window {window[0]:.4f} ms "
          f"({n_w} layers, {window[0] / max(n_w, 1):.4f} a layer), "
          f"layer/attn/global "
          f"{'nothing' if glob is None else f'{glob[0]:.4f} ms'} ({n_g} layers"
          + ("" if glob is None else f", {glob[0] / max(n_g, 1):.4f} a layer")
          + f"), of both the gates "
          f"{'nothing' if gate is None else f'{gate[0]:.4f} ms'}; "
          f"{window[1]} decode steps in the span", flush=True)
    return window
