"""Per request (last token - first token) / (tokens - 1), median over the
requests due in the window that were answered in full. Frames arrive a chunk
of 8 at a time, so single gaps are not used."""

from measure import percentile, tpot_s


def read(run):
    values = [v * 1e3 for v in (tpot_s(r) for r in run.due_in_window()
                                if r["ok"]) if v is not None]
    return (percentile(values, 50), len(values)) if values else None
