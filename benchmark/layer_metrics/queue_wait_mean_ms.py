"""Engine (runtime/batcher.py): mean wait in the engine's queue, from the
``tpusc_request_phase_seconds{phase="queue"}`` histogram's sum / count over
the window (its buckets are too coarse for a median). It moves the time to
first token, so it is reported where ``end_to_end/ttft_p90_ms.py`` is: in no
cell yet (see there)."""


def read(run):
    name = "tpusc_request_phase_seconds"
    n = run.counter(name + "_count", 'phase="queue"')
    if n <= 0:
        return None
    return run.counter(name + "_sum", 'phase="queue"') / n * 1e3, int(n)
