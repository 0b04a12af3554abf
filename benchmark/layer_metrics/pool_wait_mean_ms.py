"""Protocol (local_backend.py): mean wait for one of the serving pool's
threads, from the ``tpusc_pool_wait_seconds`` histogram's sum / count over
the window, every ``what`` together. It moves the time to first token, so it
is for the cell ``queue_wait_mean_ms.py`` is for (``mistral7b-chat-short``):
no cell of ``BENCHMARK.json`` reports it yet."""


def read(run):
    name = "tpusc_pool_wait_seconds"
    n = run.counter(name + "_count")
    if n <= 0:
        return None
    return run.counter(name + "_sum") / n * 1e3, int(n)
