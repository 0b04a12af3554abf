"""Engine (runtime/batcher.py): how many of a decode chunk's small operands
(block tables, ``tok``, ``pos``, ``active``, ``temps``, ``topks``, the chunk
counter) the chunk's launch had to upload, mean of ring ``uploads`` over the
window's boundaries that ran a chunk. The runtime keeps each operand on the
device with the host values it was made from and sends one again only when
its mirror no longer holds them: 0 on a decode-only boundary, two after a
retirement (``active`` and the freed lane's block-table row), what an
admission wrote after one. Lower = more launches that are one enqueue of
resident operands (``chunk_launch_p50_ms``). A ring without the field (a
program that uploads every operand every chunk) gives nothing."""


def read(run):
    uploads = [s["uploads"] for s in run.window_steps()
               if s["chunk"] > 0 and s.get("uploads") is not None]
    return (sum(uploads) / len(uploads), len(uploads)) if uploads else None
