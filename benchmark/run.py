#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process that owns the chip. The main thread runs the program's own
``tpuserve serve`` (``cli.main``); a client thread drives its REST sockets as
a user would, open loop, then sends the process SIGTERM so that ``serve()``
shuts down the way it does in production. Everything is found by name from
``BENCHMARK.json``: the cell in ``workloads/<cell>.json``, its configuration
in ``configs/<config>.json``, that configuration's model family (config
mapping, weight layout, plain reference) in ``families/<family>.py``, each
metric's reader in ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``.
Adding a cell, a configuration, a family or a metric adds files and entries
and edits none (README.md).

Without a TPU the run exits non-zero and prints no result line, whatever
``JAX_PLATFORMS`` says (the sandbox sets it to ``cpu`` for every process, so
that variable alone asks for nothing). ``--rehearsal``, with JAX held to the
CPU, runs the cell's ``rehearsal`` sizes through the same code and prints
sample COUNTS only, never a number under a device metric's name.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from typing import Any  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# a run that has not ended by then is killed with every thread's stack shown
# (the contract allows 360 s, 1200 s for a checkout's first run, which compiles)
DEADLINE_S = 1150.0


class BenchFailure(Exception):
    """The run cannot give a result (no chip, no room, a broken cell)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def deep_merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = deep_merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else copy.deepcopy(v)
    return out


# ---------------------------------------------------------------------------
# the cell, its configuration, its metrics: all found by name
# ---------------------------------------------------------------------------

def load_cell(name: str, rehearsal: bool) -> tuple[dict, dict, dict]:
    """-> (BENCHMARK.json, the cell's file, its configuration's file), with
    the ``rehearsal`` overrides of both files applied on the CPU."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchFailure(f"BENCHMARK.json has no workload {name!r} "
                           f"(has: {[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    cell["chips"] = entry["chips"]
    if rehearsal:
        cell = deep_merge(cell, cell.get("rehearsal", {}))
        config = deep_merge(config, config.get("rehearsal", {}))
    return bench, cell, config


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_by_name(folder: str, name: str, what: str):
    """The module ``<folder>/<name>.py`` of the benchmark, found by name."""
    path = os.path.join(HERE, folder, f"{name}.py")
    if not os.path.exists(path):
        raise BenchFailure(f"{what} {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(kind: str, name: str):
    folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
    return load_by_name(folder, name, "metric").read


def load_family(config: dict):
    """The configuration's model family: how its file's keys map to the
    program's config, which weights it has, and its plain reference."""
    return load_by_name("families", config["family"], "model family")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def device_stamp(chips: int, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    stamp = {"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devices)}
    say(f"device: platform={stamp['platform']} kind={stamp['kind']!r} "
        f"count={stamp['count']} jax={jax.__version__} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')!r}")
    if rehearsal:
        if d0.platform != "cpu":
            raise BenchFailure("--rehearsal is for the CPU, and JAX gave "
                               f"{d0.platform!r}: set JAX_PLATFORMS=cpu")
        return stamp
    if d0.platform != "tpu":
        raise BenchFailure(f"JAX gave platform {d0.platform!r}: the benchmark "
                           "measures on a TPU only (--rehearsal, with JAX held "
                           "to the CPU, runs the counts-only rehearsal)")
    if len(devices) < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX found "
                           f"{len(devices)}")
    return stamp


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts JAX's backend compilations (a persistent-cache hit is one too:
    either way a request waited for a program)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


# ---------------------------------------------------------------------------
# set-up: weights, store, server config
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tenant_names(n: int) -> list[str]:
    return [f"tenant{i:02d}" for i in range(n)]


def write_store(cell: dict, family, mc: dict,
                seed: int) -> tuple[str, list[dict], dict]:
    """Survey the directories, write every tenant's artifact -> (work
    directory, host copies of the tenants the checks compare, facts)."""
    import survey
    import weights

    n = int(cell["tenants"])
    one = family.param_bytes(mc)
    # each tenant lies once in the store and once in the server's disk cache
    workroot = survey.choose(ROOT, one, 2 * n * one, say)
    workdir = tempfile.mkdtemp(prefix="bench-work-", dir=workroot)
    t0 = time.monotonic()
    kept, nbytes, stages = weights.write_tenants(
        os.path.join(workdir, "store"), tenant_names(n), family, mc, seed,
        keep=int(cell["checks"].get("reference_tenants", 1)))
    dt = time.monotonic() - t0
    say(f"weights: {n} tenant(s), {nbytes} bytes in {dt:.2f}s "
        f"({nbytes / dt / 1e9:.2f} GB/s, made on the device from seed {seed}; "
        f"{json.dumps({k: round(v, 2) for k, v in stages.items()})}) "
        f"under {workdir}")
    return workdir, kept, {"write_s": dt, "weight_bytes": nbytes}


def write_server_config(workdir: str, server: dict, rehearsal: bool) -> tuple[str, int]:
    import yaml

    rest_port = free_port()
    conf = deep_merge(server, {
        # paths and ports only: every option that shapes the serving path is
        # in the configuration's and the cell's files, with its reason
        "cache": {"base_dir": os.path.join(workdir, "disk-cache")},
        "model_provider": {"type": "disk",
                           "base_dir": os.path.join(workdir, "store")},
        "cache_node": {"rest_port": rest_port, "grpc_port": free_port()},
        "observability": {"flight_dir": os.path.join(workdir, "flight")},
    })
    if rehearsal:
        conf.setdefault("serving", {})["platform"] = "cpu"
    path = os.path.join(workdir, "server.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(conf, f)
    return path, rest_port


# ---------------------------------------------------------------------------
# the client thread: warm-up, checks, the window
# ---------------------------------------------------------------------------

def pow2_buckets(lo: int, hi: int) -> list[int]:
    """The power-of-two prompt buckets lengths in ``[lo, hi]`` fall into."""
    out, b = [], 1
    while b < lo:
        b *= 2
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def length_range(spec: dict) -> tuple[int, int]:
    if "lognormal" in spec:
        return int(spec["lognormal"]["min"]), int(spec["lognormal"]["max"])
    return min(spec["choice"]["lens"]), max(spec["choice"]["lens"])


def decode_predict(body: bytes):
    """A ``:predict`` answer's last-token logits -> float32 ``(vocab,)``."""
    import numpy as np

    out = json.loads(body)["outputs"]
    if isinstance(out, dict):
        out = next(iter(out.values()))
    return np.asarray(out, np.float32)[0]


def stop_trace(trace_dir: str) -> None:
    """``jax.profiler.stop_trace`` without its export. The session's
    ``stop()`` returns the capture (the XSpace ``stop_and_export`` would
    write as ``.xplane.pb``); the export also renders a trace-viewer JSON of
    every event that nothing here reads, which on a half-million-operation
    span took 37 of stop_trace's 61 s (PERF.md section 6, PR 28). The
    session is JAX's private state: where it is not as expected, the public
    call does the same, slower."""
    import jax
    from jax._src import profiler as private

    state = getattr(private, "_profile_state", None)
    session = getattr(state, "profile_session", None)
    if not hasattr(session, "stop"):
        jax.profiler.stop_trace()
        return
    with state.lock:
        capture = session.stop()
        state.reset()
    out = os.path.join(trace_dir, "plugins", "profile", "bench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench.xplane.pb"), "wb") as f:
        f.write(capture)


class Driver:
    """The client thread's work. ``run`` holds what the run observed at the
    end, or ``error`` the reason there is nothing."""

    def __init__(self, args, cell: dict, config: dict, family, mc: dict,
                 server: dict, rest_port: int, kept: list[dict], stamp: dict,
                 compiles: CompileCounter, workdir: str, split: dict) -> None:
        from measure import Run

        self.args, self.cell, self.family = args, cell, family
        self.mc, self.kept, self.compiles, self.workdir = mc, kept, compiles, workdir
        self.rest_port = rest_port
        self.first_digest: dict[str, str] | None = None   # predict cells
        self.trace_dir = os.path.join(workdir, "trace")
        self.names = tenant_names(int(cell["tenants"]))
        self.run = Run(cell=cell, config=config, program_config=mc,
                       server=server, device=stamp, seconds=float(args.seconds))
        self.run.setup_split = split
        self.after_split: dict = {}     # a traced run's stages after the window
        self.t_done = 0.0               # when the client had all it asks for
        self.correct = True
        self.notes: list[str] = []
        self.error: BaseException | None = None

    def fail_check(self, msg: str) -> None:
        self.correct = False
        self.notes.append(msg)
        say(f"CHECK FAILED: {msg}")

    # -- warm-up and checks, per verb ---------------------------------------
    async def warm_generate(self, client) -> None:
        """Every prefill bucket the mix can hit and every decode chunk size
        (1, 2, 4, 8), one request at a time, then the seeded checks."""
        import numpy as np

        import reference

        traffic = self.cell["traffic"]
        rng = np.random.default_rng([self.args.seed, 0x3A])
        vocab = self.mc["vocab_size"]
        lo, hi = length_range(traffic["prompt"])
        plan = [(b if b <= hi else hi, 9) for b in pow2_buckets(lo, hi)]
        plan += [(lo, n) for n in (2, 3, 5)]
        t0 = time.monotonic()
        for plen, max_new in plan:
            rec = await client.generate(
                self.names[0], rng.integers(1, vocab, plen).tolist(), max_new)
            self.run.setup_records.append(rec)
            if not rec["ok"]:
                raise BenchFailure(f"warm-up generate failed: {rec['error']}")
        # a burst: what only concurrent rows compile or allocate
        burst = await asyncio.gather(*(
            client.generate(self.names[0], rng.integers(1, vocab, lo).tolist(), 9)
            for _ in range(4)))
        if not all(r["ok"] for r in burst):
            raise BenchFailure(f"warm-up burst failed: {[r['error'] for r in burst]}")
        self.run.setup_split["warmup_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        checks = self.cell["checks"]
        n_new = int(checks["generate_new_tokens"])
        prompts = [rng.integers(1, vocab, plen).tolist()
                   for plen in checks["generate_prompt_lens"]]
        recs = await asyncio.gather(*(
            client.generate(self.names[0], p, n_new) for p in prompts))
        good = [(p, r["tokens"]) for p, r in zip(prompts, recs)
                if r["ok"] and all(0 <= t < vocab for t in r["tokens"])]
        for r in recs:
            if not r["ok"]:
                self.fail_check(f"check generate failed: {r['error']}")
        if len(good) < len(recs):
            self.fail_check("a check request failed or left the vocabulary")
        # teacher-forced: position p-1+j of prompt+tokens predicts token j
        refs = await asyncio.to_thread(
            self.family.logits_many, self.mc, self.kept[0],
            [p + toks[:-1] for p, toks in good], n_new)
        worst = 0.0
        for (p, toks), ref in zip(good, refs):
            slack = reference.greedy_slack(ref, toks)
            worst = max(worst, slack)
            say(f"check generate: prompt {len(p)}, {n_new} greedy tokens, "
                f"worst emitted token {slack:.4f} reference-std below the "
                f"reference's largest logit (tolerance {checks['tolerance_std']})")
            if not slack <= float(checks["tolerance_std"]):
                self.fail_check(
                    f"an emitted token lies {slack:.4f} std below the "
                    f"reference's argmax (> {checks['tolerance_std']})")
        self.run.setup_split["checks_s"] = time.monotonic() - t0
        self.run.setup_split["check_worst_std"] = worst

    async def warm_predict(self, client, schedule) -> None:
        """Touch every tenant once in rising order of popularity (store ->
        disk -> HBM): the hottest end resident, the rest in the host tier, so
        every miss in the window is a host-tier return. Each tenant's first
        answer is kept: the window's answers must equal it byte for byte."""
        import numpy as np

        import reference

        prompts: dict[int, tuple] = {}
        for r in schedule:
            prompts.setdefault(r.tenant, r.prompt)
        rng = np.random.default_rng([self.args.seed, 0x3B])
        plen = length_range(self.cell["traffic"]["prompt"])[1]
        for t in range(len(self.names)):
            prompts.setdefault(t, tuple(
                rng.integers(1, self.mc["vocab_size"], plen).tolist()))
        t0 = time.monotonic()
        self.first_digest = {}
        bodies: dict[int, bytes] = {}
        for t in reversed(range(len(self.names))):
            rec = await client.predict(self.names[t], prompts[t], keep_body=True)
            self.run.setup_records.append(rec)
            if not rec["ok"]:
                raise BenchFailure(f"first touch of {self.names[t]} failed: "
                                   f"{rec['error']}")
            self.first_digest[self.names[t]] = rec["digest"]
            bodies[t] = rec.pop("body")
        self.run.setup_split["warmup_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        checks = self.cell["checks"]
        logits = {t: decode_predict(bodies[t]) for t in range(len(self.kept))}
        worst = 0.0
        for t, got in logits.items():
            ref = (await asyncio.to_thread(
                self.family.logits_many, self.mc, self.kept[t],
                [list(prompts[t])], 1))[0][0]
            err = reference.compare_logits(got, ref)
            worst = max(worst, err)
            say(f"check predict: {self.names[t]}, {len(prompts[t])} tokens, "
                f"last-token logits differ from the reference by at most "
                f"{err:.4f} reference-std (tolerance {checks['tolerance_std']})")
            if not err <= float(checks["tolerance_std"]):
                self.fail_check(f"{self.names[t]} logits are {err:.4f} std "
                                "from the reference")
        if len(logits) >= 2 and np.array_equal(logits[0], logits[1]):
            self.fail_check("two tenants with different seeds answered alike")
        # the coldest tenant was evicted by the touches after it: bring it
        # back and hold it to its first answer before the window opens
        cold = len(self.names) - 1
        rec = await client.predict(self.names[cold], prompts[cold])
        self.run.setup_records.append(rec)
        if rec["digest"] != self.first_digest[self.names[cold]]:
            self.fail_check(f"{self.names[cold]} came back from eviction with "
                            "other logits than its first answer")
        # ... and restore the order of the touches (hottest last)
        for t in reversed(range(min(len(self.names),
                                    int(self.cell["checks"].get("retouch", 0))))):
            self.run.setup_records.append(
                await client.predict(self.names[t], prompts[t]))
        self.run.setup_split["checks_s"] = time.monotonic() - t0
        self.run.setup_split["check_worst_std"] = worst

    # -- the traced part of the window --------------------------------------
    async def trace_part(self, t0: float) -> None:
        import jax

        span = float(self.cell.get("trace_s", 4.0))
        start = t0 + min(float(self.cell.get("trace_at_s", 0.4 * self.run.seconds)),
                         max(0.0, self.run.seconds - span - 1.0))
        await asyncio.sleep(max(0.0, start - time.monotonic()))
        # the device's operations and the benchmark's own annotation are all
        # that is read: Python's call tracer would cost the host what the
        # window is there to measure
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        await asyncio.to_thread(jax.profiler.start_trace, self.trace_dir,
                                profiler_options=options)
        w0 = time.time()
        with jax.profiler.TraceAnnotation(f"bench_wall_{time.time_ns()}"):
            pass
        await asyncio.sleep(span)
        w1 = time.time()
        t = time.monotonic()
        await asyncio.to_thread(stop_trace, self.trace_dir)
        self.after_split["trace_stop_s"] = time.monotonic() - t
        self.run.trace_wall = (w0, w1)

    # -- the whole drive ------------------------------------------------------
    async def drive(self) -> None:
        import client as client_mod
        import traffic

        run, cell = self.run, self.cell
        async with client_mod.Client(
                self.rest_port, float(cell.get("request_timeout_s", 900))) as client:
            await client.wait_ready()
            run.setup_split["server_up_s"] = time.monotonic() - T_START
            horizon = run.seconds
            schedule = traffic.compile_schedule(
                cell["traffic"], self.args.seed, self.mc["vocab_size"], horizon)
            say(f"schedule: {json.dumps(traffic.describe(schedule))}")
            if cell["traffic"].get("verb", "generate") == "generate":
                await self.warm_generate(client)
            else:
                await self.warm_predict(client, schedule)

            run.before = await client.observe()
            run.setup_s = time.monotonic() - T_START
            say("setup split: " + json.dumps(
                {k: round(v, 3) for k, v in run.setup_split.items()}))
            run.t0 = time.monotonic()
            tracer = (asyncio.create_task(self.trace_part(run.t0))
                      if self.args.trace else None)
            run.records = await client_mod.replay(
                client, schedule, self.names, run.t0, run.seconds,
                float(cell.get("drain_s", 0.0)))
            if tracer is not None:
                await tracer
            run.t_end = time.monotonic()
            run.after = await client.observe()
            run.steps = await client.engine_steps()
            run.compiles_in_window = self.compiles.between(
                run.t0, run.t0 + run.seconds)
        self.t_done = time.monotonic()
        if self.first_digest is not None:
            bad = [r for r in run.records
                   if r["ok"] and r["digest"] != self.first_digest[r["tenant"]]]
            if bad:
                self.fail_check(
                    f"{len(bad)} answers differ from their tenant's first "
                    f"answer (e.g. {bad[0]['tenant']} request {bad[0]['index']})")

    def thread_main(self, stop: threading.Event) -> None:
        try:
            asyncio.run(self.drive())
        except BaseException as e:  # noqa: BLE001 - reported by main()
            traceback.print_exc()
            self.error = e
        finally:
            if not stop.is_set():
                os.kill(os.getpid(), signal.SIGTERM)


def serve_with_client(config_path: str, driver: Driver) -> int:
    """Main thread: the CLI's ``serve``. Client thread: the drive, then
    SIGTERM to this process — the signal ``serve()`` itself handles."""
    from tfservingcache_tpu.cli import main as cli_main

    stop = threading.Event()
    thread = threading.Thread(target=driver.thread_main, args=(stop,),
                              name="bench-client")
    thread.start()
    try:
        rc = cli_main(["--config", config_path, "serve"])
    finally:
        stop.set()
        thread.join(timeout=120.0)
    if thread.is_alive():
        raise BenchFailure("the client thread did not finish")
    return rc


def threads_left() -> list[str]:
    deadline = time.monotonic() + 20.0
    while True:
        alive = [t for t in threading.enumerate()
                 if t is not threading.main_thread() and t.is_alive()
                 and not t.daemon]
        if not alive or time.monotonic() > deadline:
            return [t.name for t in alive]
        time.sleep(0.2)


# ---------------------------------------------------------------------------
# after the window: trace, metrics, the result line
# ---------------------------------------------------------------------------

def host_states(run) -> list[tuple[str, float, float]]:
    """What the host was doing, on the wall clock, for naming idle gaps:
    engine boundaries from the ring (``t_wall`` is a boundary's end,
    ``step_ms`` its length), then requests in flight from the client."""
    states = []
    for s in run.steps:
        end = s["t_wall"]
        what = ("engine boundary with admission/prefill" if s.get("admitted")
                else "engine boundary, decode chunk only")
        states.append((what, end - s["step_ms"] / 1e3, end))
    mono_to_wall = run.before["t_wall"] - run.before["t"]
    for r in run.records:
        end = r["end"] or (r["token_t"][-1] if r["token_t"] else run.t_end)
        states.append(("request in flight, no engine boundary open",
                       r["sent"] + mono_to_wall, end + mono_to_wall))
    if run.trace_wall:
        states.append(("waiting for a request", *run.trace_wall))
    return states


def reduce_trace(driver: Driver) -> None:
    import trace_reduce

    path = trace_reduce.find_xplane(driver.trace_dir)
    t0 = time.monotonic()
    rows = trace_reduce.load_xplane(path)
    t1 = time.monotonic()
    trace = driver.run.trace = trace_reduce.reduce(rows, host_states(driver.run))
    driver.after_split.update(
        trace_load_s=t1 - t0, trace_reduce_s=time.monotonic() - t1,
        capture_bytes=os.path.getsize(path),
        device_events=sum(k["calls"] for k in trace["kernels"].values()))


def read_metrics(bench: dict, cell_name: str, kind: str, run,
                 rehearsal: bool) -> dict:
    from measure import samples

    out = {}
    for m in cell_metrics(bench, cell_name, kind):
        value, n = samples(load_reader(kind, m["name"])(run))
        if value is None:
            say(f"metric {m['name']}: nothing to read, left out")
            continue
        shown = ("a value, withheld: a CPU run gives counts only" if rehearsal
                 else f"{value!r} {m['unit']}")
        say(f"metric {m['name']} = {shown}"
            + (f" over {n} samples" if n is not None else ""))
        if rehearsal:
            out[f"rehearsal.{m['name']}.samples"] = {
                "value": 1 if n is None else n, "unit": "count"}
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU, counts only (never a "
                    "device number); the driver never passes it")
    args = ap.parse_args(argv)

    faulthandler.enable()

    def abort() -> None:
        say(f"benchmark: no end after {DEADLINE_S:.0f}s — giving up")
        faulthandler.dump_traceback(all_threads=True)
        os._exit(4)

    watchdog = threading.Timer(DEADLINE_S, abort)
    watchdog.daemon = True
    watchdog.start()
    workdir = None
    try:
        if importlib.util.find_spec("tfservingcache_tpu") is None:
            raise BenchFailure("the program (tfservingcache_tpu) is not beside "
                               "the benchmark: nothing to measure")
        rehearsal = args.rehearsal
        bench, cell, config = load_cell(args.workload, rehearsal)
        if args.seconds is None:
            args.seconds = float(bench["run_seconds"])
        if rehearsal:
            args.seconds = min(args.seconds,
                               float(cell.get("rehearsal_seconds", 4.0)))
        stamp = device_stamp(int(cell["chips"]), rehearsal)

        from tfservingcache_tpu.ops import attention
        from tfservingcache_tpu.utils import compile_cache

        cache_dir = compile_cache.configure()
        before = compile_cache.entry_count(cache_dir)
        say(f"compile cache: {cache_dir}, {before} entries before")
        compiles = CompileCounter()
        family = load_family(config)
        try:
            mc = family.program_config(config)
        except (KeyError, ValueError) as e:
            raise BenchFailure(f"configuration {config['name']!r} does not "
                               f"fit family {config['family']!r}: {e!r}")
        server = deep_merge(config.get("server", {}), cell.get("server", {}))
        say(f"model: {config['name']} ({config['family']}) as run: "
            f"{json.dumps(mc)}")
        say(f"server options set (all others are the program's defaults): "
            f"{json.dumps(server)}")
        workdir, kept, split = write_store(cell, family, mc, args.seed)
        config_path, rest_port = write_server_config(workdir, server, rehearsal)
        driver = Driver(args, cell, config, family, mc, server, rest_port, kept,
                        stamp, compiles, workdir, split)
        rc = serve_with_client(config_path, driver)
        driver.after_split["shutdown_s"] = time.monotonic() - driver.t_done
        if driver.error is not None:
            raise BenchFailure(f"the client failed: {driver.error!r}")
        if rc != 0:
            raise BenchFailure(f"`tpuserve serve` returned {rc}")
        left = threads_left()
        if left:
            raise BenchFailure(f"threads outlived the server: {left}")

        run = driver.run
        if args.trace:
            reduce_trace(driver)
        say("attention dispatch tally (gate, branch, reason): traces")
        for key, n in sorted(attention.dispatch_tally().items()):
            say(f"  {key}: {n}")
        say(f"compile cache: {compile_cache.entry_count(cache_dir) - before} "
            f"entries added; compilations inside the window: "
            f"{run.compiles_in_window} (expected 0)")
        window = run.due_in_window()
        late = sorted(r["sent"] - r["due"] for r in window)
        if late:
            say(f"generator lateness over {len(late)} requests: p50 "
                f"{late[len(late) // 2] * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms")
        # a request still unanswered when the drain ended has failed like any
        # other: were it left out, a change that starves its slow streams
        # would drop them from the medians and read as a gain
        failed = [r for r in window if not r["ok"]]
        unanswered = sum(r["cancelled"] for r in failed)
        for r in failed[:5]:
            say(f"failed request {r['index']} ({r['tenant']}): status "
                f"{r['status']}: " + (r["error"] or "unanswered "
                f"{run.t_end - r['due']:.1f}s after it was due, "
                f"{len(r['token_t'])} of {r['max_new']} tokens received"))
        say(f"requests due in the window: {len(window)}, answered in full "
            f"{len(window) - len(failed)}, failed {len(failed)} (of them "
            f"{unanswered} still unanswered when the run stopped waiting)")
        if run.compiles_in_window:
            driver.notes.append(
                f"{run.compiles_in_window} compilations inside the window")
        peak = memory_peak_bytes()
        say(f"peak device bytes: {peak}")

        kind = "per_layer" if args.trace else "end_to_end"
        t_readers = time.monotonic()
        metrics = read_metrics(bench, args.workload, kind, run, rehearsal)
        driver.after_split["readers_s"] = time.monotonic() - t_readers
        device = dict(stamp, memory_peak_bytes=peak)
        result = {"correct": bool(driver.correct and not failed),
                  "attempted": len(window), "failed": len(failed),
                  "metrics": metrics, "device": device}
        if args.trace and run.trace is not None and not rehearsal:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            result["breakdown"] = {
                "device_ops": run.trace["device_ops"][:10],
                "idle_gaps": run.trace["idle_gaps"][:10]}
            say(f"longest single gaps: {json.dumps(run.trace['longest_gaps'][:5])}")
        for note in driver.notes:
            say(f"note: {note}")
        if args.trace:
            # what a traced run costs beyond an untraced one (README.md,
            # "What a run costs"): the limit is on the whole process
            driver.after_split["process_wall_s"] = time.monotonic() - T_START
            say("after-window split: " + json.dumps(
                {k: round(v, 3) for k, v in driver.after_split.items()}))
        print(json.dumps(result), flush=True)
        return 0
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        watchdog.cancel()
        if workdir is not None:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
