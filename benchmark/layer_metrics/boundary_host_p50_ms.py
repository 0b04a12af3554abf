"""Engine (runtime/batcher.py): a boundary's time outside the prefills and
the decode chunk, median of ring ``step_ms - prefill_ms - chunk_ms`` over the
window's boundaries that ran a chunk: admission scan, page accounting, the
arena insert's dispatch, emission, ring and ledger writes. A ring without
the fields gives nothing."""

from measure import percentile


def read(run):
    host = [s["step_ms"] - s["prefill_ms"] - s["chunk_ms"]
            for s in run.window_steps()
            if s["chunk"] > 0 and s.get("chunk_ms") is not None]
    return (percentile(host, 50), len(host)) if host else None
