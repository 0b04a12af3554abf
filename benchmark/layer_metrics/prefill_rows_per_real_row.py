"""Model step (models/generation.py): rows the window's admission prefills
computed in their token-wise stages a REAL prompt row,
``tpusc_prefill_rows_total{kind="computed"}`` over ``{kind="real"}``. An
admission pads its prompt to a power-of-two bucket; since PR 48 a long
prefill's projections, MLPs and gates run over the row blocks that hold real
rows (``models/real_rows``), so this reads what pad is still paid; the line it
prints shows ``bucket`` over ``real`` beside it, what the pad was. 1.0 is a
prefill that computes its prompt and nothing else. A program without the
counter (older than PR 48) gives nothing."""


def read(run):
    name = "tpusc_prefill_rows_total"
    real = run.counter(name, 'kind="real"')
    if real <= 0:
        return None
    computed = run.counter(name, 'kind="computed"')
    bucket = run.counter(name, 'kind="bucket"')
    print(f"prefill rows: {computed:.0f} computed and {bucket:.0f} in the "
          f"buckets for {real:.0f} real: {computed / real:.3f} computed a "
          f"real row where the bucket holds {bucket / real:.3f}", flush=True)
    return computed / real, int(real)
