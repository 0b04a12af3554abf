"""Protocol (protocol/rest.py, local_backend.py): the self time of set-up:
``run.setup_s`` less the harness's own parts (what lies before the server's
start, its checks) less every part the program names (``server_start``, the
loads' stages, ``engine_build``, every program's build seconds, the
``first_run``s). What is left no span covers: the warm-up requests' own
serving (prefills and decode chunks that built nothing), HTTP, the hops between
threads. Prints the whole table once; the parts add up to ``setup_s``."""

from setup_account import build_seconds, setup_parts, total


def read(run):
    parts = setup_parts(run)
    if parts is None:
        return None
    inside = build_seconds(run, inside_checks=True) or {}
    print(f"setup table (setup_s {run.setup_s:.2f}): "
          + ", ".join(f"{k} {v:.2f}" for k, v in parts.items())
          + f"; inside harness_checks_s, the reference's own builds: "
          f"{total(inside, 'trace', 'lower', 'compile', 'cache_load'):.2f}; "
          f"the harness's split: "
          + ", ".join(f"{k} {v:.2f}" for k, v in run.setup_split.items()
                      if k.endswith("_s")), flush=True)
    return parts["unexplained_s"]
