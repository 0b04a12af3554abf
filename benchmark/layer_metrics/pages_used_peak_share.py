"""Arena (SlotDecodeState): the largest share of the arena's pages in use at
a boundary of the window, ring ``pages_used / (pages_used + pages_free)``."""


def read(run):
    shares = [100.0 * s["pages_used"] / (s["pages_used"] + s["pages_free"])
              for s in run.window_steps()
              if s["pages_used"] + s["pages_free"] > 0]
    return (max(shares), len(shares)) if shares else None
