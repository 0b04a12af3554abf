"""Scenario lab (ISSUE 17): composable workload specs, a fault injector,
and SLO scorecards over the existing serving machinery.

Three pieces, deliberately decoupled from production wiring:

* ``workload`` — a declarative DSL (tenant mix x zipf skew x arrival
  process x prompt-length mix x multi-turn depth) compiled to a seeded,
  replayable request schedule;
* ``faults`` — a process-global injector with pluggable hook sites in the
  engine, cache manager, peer-transfer receiver, and fleet status plane.
  Disarmed (the default) every hook is a passthrough; arming happens only
  through ``observability.lab_faults`` / the ``TPUSC_OBSERVABILITY_LAB_FAULTS``
  env override or an explicit ``arm()`` in tests and drives;
* ``scenario`` — runs one scenario x fault cell end-to-end and emits an
  SLO scorecard row (TTFT percentiles, tok/s, goodput, cold-miss rate,
  lost/recovered counts, page-conservation census, platform stamps).

This ``__init__`` intentionally imports nothing: production modules import
``tfservingcache_tpu.lab.faults`` for their hook sites, and that must not
drag numpy-heavy workload compilation into the server's import graph.
"""
