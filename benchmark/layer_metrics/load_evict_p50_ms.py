"""Runtime load (runtime/model_runtime.py): what making room costs a cold
request, its ``evict`` spans under ``load{tier=host}`` summed (one a victim),
median over the window's requests whose load evicted."""

from measure import load_children, percentile


def read(run):
    values = [sum(evicts) * 1e3 for r in run.due_in_window()
              if (evicts := load_children(r, "host", "evict"))]
    return (percentile(values, 50), len(values)) if values else None
