"""Runtime load (runtime/model_runtime.py): seconds of the
``device_transfer`` span under ``load{tier=host}``, median over the window's
requests that carried one."""

from measure import load_children, percentile


def read(run):
    values = [v for r in run.due_in_window()
              for v in load_children(r, "host", "device_transfer")]
    return (percentile(values, 50), len(values)) if values else None
