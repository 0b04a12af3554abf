"""Model step (models/generation.py): seconds XLA compiled, or the persistent
compilation cache loaded, the programs set-up built:
``tpusc_program_build_seconds_total{stage="compile"}`` + ``{stage=
"cache_load"}`` at the window's start. Prints hits against misses
(``tpusc_program_builds_total``) and, by name, any build INSIDE the window."""

from setup_account import BUILDS, build_seconds, dearest, samples, snapshot, total


def read(run):
    builds = build_seconds(run)
    if builds is None:
        return None
    by_cache: dict[str, float] = {}
    for lab, n in samples(snapshot(run), BUILDS):
        by_cache[lab.get("cache", "?")] = by_cache.get(lab.get("cache", "?"), 0) + n
    print(f"setup compile: compile {total(builds, 'compile'):.2f} s, cache_load "
          f"{total(builds, 'cache_load'):.2f} s; builds by cache "
          f"{ {k: int(v) for k, v in sorted(by_cache.items())} }; the dearest: "
          f"{dearest(builds, 'compile', 'cache_load')}", flush=True)
    if run.after and run.counter(BUILDS) > 0:
        before = {tuple(sorted(lab.items())): n
                  for lab, n in samples(snapshot(run), BUILDS)}
        late = []
        for lab, n in samples(run.after.get("prom", {}), BUILDS):
            grown = int(n - before.get(tuple(sorted(lab.items())), 0))
            if grown > 0:
                late.append(f"{lab.get('program')} ({lab.get('cache')}) x{grown}")
        print(f"setup compile: BUILDS INSIDE THE WINDOW: {', '.join(late)}",
              flush=True)
    return total(builds, "compile", "cache_load"), int(sum(by_cache.values()))
