"""Protocol (protocol/rest.py, local_backend.py): a request's time in the
protocol layer itself, the root ``rest`` span's duration minus its
``pool_wait``, ``ensure_servable`` and ``infer`` spans (JSON parse and encode,
the event loop's hops), median over the window's answered requests whose
trace came back. Without ``pool_wait`` spans (a program older than them) the
difference would hold the pool's wait too, so it gives nothing."""

from client import find_spans
from measure import percentile


def read(run):
    values = []
    for r in run.due_in_window():
        root = r.get("span")
        if not r["ok"] or not root or root.get("name") != "rest":
            continue
        waits = find_spans(root, "pool_wait")
        if not waits:
            continue
        inner = waits + find_spans(root, "ensure_servable") + find_spans(root, "infer")
        values.append((root["duration_s"] - sum(s["duration_s"] for s in inner)) * 1e3)
    return (percentile(values, 50), len(values)) if values else None
