"""Cache (host_tier.py): share of the window's reloads (every tier but HBM)
that came back from the host tier."""


def read(run):
    name = "tpusc_reload_source_total"
    reloads = run.counter(name) - run.counter(name, 'tier="hbm"')
    if reloads <= 0:
        return None
    return 100.0 * run.counter(name, 'tier="host"') / reloads, int(reloads)
