"""Engine (runtime/batcher.py): the share of the window's decode chunks the
device went into without waiting for the host, mean of ring ``ahead`` over the
window's boundaries that ran a chunk. ``ahead`` is 1 where the chunk a boundary
fetched had been launched BEFORE the chunk before it was fetched (the engine
keeps one chunk in flight across a decode-only boundary), 0 where it was
launched at its own boundary, after the last fetch: the first chunk after an
admission, after a retirement, after the engine waited for a request, a
speculation round, every chunk on a mesh. Higher = fewer chunks the device
stood still before (``chunk_gap_p50_ms`` is how long). Low with long idle
stretches is healthy; low under steady streams means something rewrites a
mirror or sits in the queue unadmittable. A ring without the field (a program
that fetches every chunk before it launches the next) gives nothing."""


def read(run):
    ahead = [s["ahead"] for s in run.window_steps()
             if s["chunk"] > 0 and s.get("ahead") is not None]
    return (sum(ahead) / len(ahead), len(ahead)) if ahead else None
