"""Due time -> complete ``:predict`` answer, median over the requests due in
the window whose own ``x-tpusc-trace`` holds a ``load`` span: the program's
word for "this request loaded a model", never a latency threshold."""

from measure import load_tiers, percentile


def read(run):
    values = [r["end"] - r["due"] for r in run.due_in_window()
              if r["ok"] and load_tiers(r)]
    return (percentile(values, 50), len(values)) if values else None
