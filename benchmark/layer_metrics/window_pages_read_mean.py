"""Arena (SlotDecodeState): the pages a WINDOW layer's decode call read a live
lane, mean over the window's boundaries that ran a decode chunk (ring
``window_pages`` where ``chunk > 0``): worked out on the engine thread from the
``pos`` / ``active`` mirrors the chunk was dispatched with, the pages that hold
each live lane's last ``window`` tokens (at most a ring's, 65 at a window of
1024 and pages of 16, whatever the request's length; a global layer's call
reads ``tokens / 16``). A program whose ring has no such field, or a model
with no window layer (the field is 0), gives nothing."""


def read(run):
    pages = [s["window_pages"] for s in run.window_steps()
             if s["chunk"] > 0 and s.get("window_pages")]
    return (sum(pages) / len(pages), len(pages)) if pages else None
