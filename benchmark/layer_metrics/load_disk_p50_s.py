"""Runtime load: wall seconds of ``load{tier=disk}``, median over set-up's
first touches (store -> disk cache -> HBM; the window itself has no disk
load while the host tier holds every tenant)."""

from client import find_spans
from measure import percentile


def read(run):
    values = [s["duration_s"] for r in run.setup_records
              for s in find_spans(r.get("span"), "load")
              if str(s.get("attrs", {}).get("tier")) == "disk"]
    return (percentile(values, 50), len(values)) if values else None
