"""Protocol (protocol/rest.py, local_backend.py): due -> complete answer,
median over the window's requests with no ``load`` span: the floor a cold
request adds its load to."""

from measure import load_tiers, percentile


def read(run):
    values = [(r["end"] - r["due"]) * 1e3 for r in run.due_in_window()
              if r["ok"] and not load_tiers(r)]
    return (percentile(values, 50), len(values)) if values else None
