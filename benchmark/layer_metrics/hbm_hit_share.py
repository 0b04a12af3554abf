"""Cache (cache/manager.py): share of the window's requests that found their
model in HBM, ``tpusc_reload_source_total{tier="hbm"}`` over all tiers."""


def read(run):
    name = "tpusc_reload_source_total"
    total = run.counter(name)
    if total <= 0:
        return None
    return 100.0 * run.counter(name, 'tier="hbm"') / total, int(total)
