"""Benchmark client: how late requests left (sent - due), 95th percentile.
Large against the metric it moves = the generator starved and the run is
void."""

from measure import percentile


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.due_in_window()]
    return (percentile(late, 95), len(late)) if late else None
