"""Model step: prefill wall milliseconds per 1000 prompt tokens, tenant
ledger ``prefill_step_seconds / tokens_in`` over the window."""


def read(run):
    tokens = run.ledger("tokens_in")
    if tokens <= 0:
        return None
    return run.ledger("prefill_step_seconds") / tokens * 1e6, int(tokens)
