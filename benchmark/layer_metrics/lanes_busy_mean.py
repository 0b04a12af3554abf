"""Engine: mean decoding lanes over the window's boundaries that ran a decode
chunk (ring ``active`` where ``chunk > 0``)."""


def read(run):
    active = [s["active"] for s in run.window_steps() if s["chunk"] > 0]
    return (sum(active) / len(active), len(active)) if active else None
