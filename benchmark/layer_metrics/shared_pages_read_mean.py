"""Arena (SlotDecodeState): the pages the decode calls over a SHARED global
layer read a live lane a step, summed over the layers that read it, mean over
the window's boundaries that ran a decode chunk (ring ``shared_pages`` where
``chunk > 0``): worked out on the engine thread from the ``pos`` / ``active``
mirrors the chunk was dispatched with, ``tokens / 16`` pages a reader, 8
readers in ``phi4flash-reasoning-steady`` (a window layer's call reads at most
a ring, ``window_pages_read_mean``). A program whose ring has no such field,
or a model in which every layer reads its own rows (the field is 0), gives
nothing."""


def read(run):
    pages = [s["shared_pages"] for s in run.window_steps()
             if s["chunk"] > 0 and s.get("shared_pages")]
    return (sum(pages) / len(pages), len(pages)) if pages else None
