#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python3 chip_smoke.py          # from the root of a checkout, on a TPU host

One process owns the chip. The main thread calls the CLI's own
``main([..., "serve"])``; a client thread drives the REST and gRPC sockets a
user would, then sends the process SIGTERM so ``serve()`` shuts down the way
it does in production. Afterwards the same process reads what only it can
see: which attention branches were traced, what the server logged, which
threads are still alive, what the compile cache holds.

The model is ``transformer_lm`` at the widths of ``LLAMA7B_CONFIG`` (d_model
4096, 32 heads of 128, d_ff 11008, vocab 32000, bf16). Depth is the only cut:
8 of 32 layers, and fewer only where no directory of the machine accepts a
file as long as 8 layers' weights (setup says which it found and why).
Weights are random, made from a seed inside this command. This is a smoke
shape, not a benchmark configuration: it prints set-up seconds as facts and
no rate, utilization or peak.

Phases (each prints OK or FAIL; any FAIL makes the exit code non-zero):
device, setup, serve (cold_miss, generate, churn, engine run inside it),
no_fallback, shutdown, reference, four_chips (only with >= 4 devices),
compile_cache.

The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``,
printed only when every phase passed. Without an accelerator — or without
the rest of the repo beside this file — it exits non-zero in seconds and
prints no such line. It sets no ``JAX_PLATFORMS`` itself.
"""

from __future__ import annotations

import base64
import dataclasses
import errno
import faulthandler
import gc
import json
import logging
import os
import re
import shutil
import signal
import socket
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request
from typing import Any, Callable

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class SmokeConfig:
    """What the smoke runs. ``main()`` uses the defaults; the CPU-harness
    test (tests/test_chip_smoke.py) passes a tiny model and ``platform="cpu"``
    to drive the same phases without a chip."""

    platform: str = "tpu"            # the device phase insists on this
    widths: dict | None = None       # None = LLAMA7B_CONFIG's widths
    n_layers: int = 8                # the depth cut (LLAMA7B_CONFIG has 32)
    prompt_tokens: int = 128         # >= 128 opens the flash gate on :predict
    max_new_tokens: int = 17         # 1 prefill token + two 8-step chunks
    predict_batch: int = 2
    generate_slots: int = 8
    kv_page_tokens: int = 16
    kv_arena_pages: int = 512        # 8192 tokens of KV: ~1 GiB at 8 layers
    require_kernels: bool = True     # flash + paged decode must be traced
    request_timeout_s: float = 900.0
    deadline_s: float = 1150.0       # whole-run watchdog (contract: 1200 s)


class SmokeFailure(Exception):
    """A check that did not hold. (Not ``assert``: ``-O`` strips those.)"""


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def size(nbytes: float) -> str:
    return (f"{nbytes / 2**30:.2f} GiB" if nbytes >= 2**28
            else f"{int(nbytes)} bytes")


def in_parallel(jobs: dict[str, Callable[[], Any]]) -> dict[str, Any]:
    """Run ``jobs`` on one thread each -> {name: result}; the first failure
    is raised once all have ended. Prints each job's seconds."""
    results: dict[str, Any] = {}
    errors: dict[str, BaseException] = {}

    def work(name: str, fn: Callable[[], Any]) -> None:
        t0 = time.monotonic()
        try:
            results[name] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors[name] = e
        say(f"  {name}: {time.monotonic() - t0:.2f}s")

    threads = [threading.Thread(target=work, args=job, name=f"smoke-{job[0]}")
               for job in jobs.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for name, err in errors.items():
        raise SmokeFailure(f"{name} failed: {type(err).__name__}: {err}") \
            from err
    return results


class Phases:
    """Named phases with an OK/FAIL line each; nothing is swallowed — a
    phase that raises is a FAIL with its traceback on stderr."""

    def __init__(self) -> None:
        self.results: list[tuple[str, bool]] = []
        self._lock = threading.Lock()

    def run(self, name: str, fn: Callable[..., Any], *args: Any) -> Any:
        say(f"--- {name}")
        t0 = time.monotonic()
        try:
            out = fn(*args)
        except Exception as e:  # noqa: BLE001 - recorded as the phase's FAIL
            traceback.print_exc()
            with self._lock:
                self.results.append((name, False))
            say(f"{name}: FAIL after {time.monotonic() - t0:.1f}s "
                f"({type(e).__name__}: {e})")
            return None
        with self._lock:
            self.results.append((name, True))
        say(f"{name}: OK ({time.monotonic() - t0:.1f}s)")
        return out if out is not None else True

    def skip(self, name: str, why: str) -> None:
        with self._lock:
            self.results.append((name, False))
        say(f"{name}: FAIL (not run: {why})")

    @property
    def ok(self) -> bool:
        with self._lock:
            return bool(self.results) and all(ok for _, ok in self.results)

    def failed(self) -> list[str]:
        with self._lock:
            return [n for n, ok in self.results if not ok]


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(cfg: SmokeConfig) -> dict:
    import jax

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - a missing wheel is itself the answer
        libtpu = "not installed"
    say(f"jax {jax.__version__}, libtpu {libtpu}, python "
        f"{sys.version.split()[0]}, JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS', '<unset>')!r}")
    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}
    say(f"device: platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']}")
    check(
        d0.platform == cfg.platform,
        f"JAX gave platform {d0.platform!r}, the smoke needs "
        f"{cfg.platform!r}"
        + (" — JAX_PLATFORMS=cpu is set in this environment"
           if os.environ.get("JAX_PLATFORMS") == "cpu" else ""),
    )
    return info


# ---------------------------------------------------------------------------
# phase: setup (package, native tier, compile cache, artifacts, config)
# ---------------------------------------------------------------------------

class LogCollector(logging.Handler):
    """Every ``tpusc.*`` record of the run, for the no_fallback scan (the
    CLI's own handler keeps writing them to stderr)."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.records: list[tuple[int, str, str]] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(
            (record.levelno, record.name, record.getMessage())
        )


@dataclasses.dataclass
class Setup:
    workdir: str
    model_config: dict
    cache_dir: str
    cache_entries_before: int
    logs: LogCollector
    tenants: tuple[str, str] = ("tenant0", "tenant1")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Where the weights can live. The first run of this script by the driver
# ended in setup with EFBIG ("File too large") on a 3.26 GiB params.bin, on a
# kind of machine whose /tmp had taken that file in every run before: a size
# limit is a property of the directory AND of whoever started the machine, and
# no getrlimit of this process shows one that a sandbox's file server inherited.
# So each candidate directory is asked, the weights go where they fit, and
# only if no directory takes a file that long is the depth cut further.

# two tenants, each once in the store and once in the server's disk cache
WEIGHT_COPIES = 4
# errnos with which a directory says "not here" rather than "you are wrong"
NO_ROOM = (errno.EFBIG, errno.ENOSPC, errno.EDQUOT)


def max_file_bytes(directory: str, want: int) -> int:
    """The longest file, up to ``want`` bytes, that ``directory`` accepts.
    Asked with a sparse file: a limit refuses the offset, not the data, so a
    byte written at the end meets it without filling the disk."""
    fd, path = tempfile.mkstemp(prefix="tpusc-smoke-probe-", dir=directory)
    try:
        def accepts(size: int) -> bool:
            try:
                os.ftruncate(fd, size)
                os.pwrite(fd, b"\0", size - 1)
            except OSError as e:
                if e.errno not in NO_ROOM:
                    raise
                return False
            finally:
                os.ftruncate(fd, 0)
            return True

        if accepts(want):
            return want
        lo, hi = 0, want            # accepts(lo), not accepts(hi)
        while hi - lo > max(1, want >> 12):
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if accepts(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)
        os.unlink(path)


def ram_backed(directory: str) -> bool:
    """Whether ``directory`` is on a tmpfs: what is written there is taken
    from the memory the server's host tier needs too."""
    best, fstype = "", ""
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mount, kind = line.split()[:3]
            if (len(mount) > len(best)
                    and os.path.commonpath((directory, mount)) == mount):
                best, fstype = mount, kind
    return fstype in ("tmpfs", "ramfs")


def mem_available() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) << 10
    raise SmokeFailure("/proc/meminfo has no MemAvailable line")


def survey_rooms(want_file: int) -> list[dict]:
    """One entry for each directory the weights could go to, in order of
    preference (disks before memory, then by free space): its path, the bytes
    it may take, and the longest file it accepts (up to ``want_file``)."""
    rooms = []
    places = (tempfile.gettempdir(), HERE, "/var/tmp", "/dev/shm")
    for path in dict.fromkeys(os.path.realpath(p) for p in places):
        if not (os.path.isdir(path) and os.access(path, os.W_OK | os.X_OK)):
            continue
        ram = ram_backed(path)
        free = shutil.disk_usage(path).free
        rooms.append({"path": path, "ram": ram,
                      "free": min(free, mem_available()) if ram else free,
                      "max_file": max_file_bytes(path, want_file)})
    # (two paths of one st_dev are kept apart: under a sandbox's file server
    # they may lie on different file systems of the host)
    return sorted(rooms, key=lambda r: (r["ram"], -r["free"]))


def artifact_bytes(model_config: dict, n_layers: int) -> int:
    """Bytes of one tenant's params.bin at ``n_layers`` (from the shapes)."""
    import jax
    import numpy as np

    from tfservingcache_tpu.models.registry import build

    model = build("transformer_lm", {**model_config, "n_layers": n_layers})
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    stored = model.store_param_dtype
    return sum(
        int(np.prod(a.shape)) * np.dtype(
            stored if stored and a.dtype.kind == "f" else a.dtype).itemsize
        for a in shapes)


def export_tenants(cfg: SmokeConfig, model_config: dict,
                   full_depth: int | None) -> str:
    """Write the two tenants' artifacts under a fresh work directory ->
    that directory; ``model_config['n_layers']`` is set to the depth used."""
    from tfservingcache_tpu.models.registry import export_artifact

    depths = sorted({d for d in (cfg.n_layers, 4, 2, 1) if d <= cfg.n_layers},
                    reverse=True)
    sizes = {d: artifact_bytes(model_config, d) for d in depths}
    try:
        import resource

        limit = resource.getrlimit(resource.RLIMIT_FSIZE)
    except (ImportError, OSError):
        limit = ("unknown",)
    want = sizes[cfg.n_layers]
    say(f"one tenant at {cfg.n_layers} layers is a file of {size(want)}, "
        f"and {WEIGHT_COPIES} copies of it are written; RLIMIT_FSIZE of this "
        f"process: {limit} (-1 = none)")
    rooms = survey_rooms(want)
    for r in rooms:
        say(f"  {r['path']}: {'memory' if r['ram'] else 'disk'}, room for "
            f"{size(r['free'])}, longest file "
            f"{'>= ' if r['max_file'] == want else ''}{size(r['max_file'])}")

    def room_needed(room: dict, nbytes: int) -> float:
        # in memory, beside the files: the host tier's copy of both tenants,
        # the exports' and the loader's transient copies, and the process
        return (1.1 * WEIGHT_COPIES * nbytes
                + (5 * nbytes + (4 << 30) if room["ram"] else 0))

    plan = [(r, d) for d in depths for r in rooms
            if r["max_file"] >= sizes[d]
            and r["free"] >= room_needed(r, sizes[d])]
    check(plan, "no directory can hold the weights even at 1 layer: "
          f"{rooms} (files needed: { {d: sizes[d] for d in depths} })")

    for room, depth in plan:
        model_config["n_layers"] = depth
        say(f"depth cut to {depth} of {full_depth} layers (widths untouched)"
            + ("" if depth == cfg.n_layers else
               f" — below the {cfg.n_layers} this smoke asks for: no "
               f"directory takes {WEIGHT_COPIES} files of {size(want)}"))
        workdir = tempfile.mkdtemp(prefix="tpusc-smoke-", dir=room["path"])
        say(f"workdir: {workdir}")
        # two tenants of one family with different seeds: different weights
        # behind one shared executable. Exported side by side (the CPU init
        # releases the GIL), on the host backend — export_artifact's own rule.
        store = os.path.join(workdir, "store")
        t0 = time.monotonic()
        try:
            in_parallel({
                f"export {name}": lambda name=name, seed=11 + i:
                    export_artifact("transformer_lm", store, name=name,
                                    version=1, config=model_config, seed=seed)
                for i, name in enumerate(("tenant0", "tenant1"))
            })
        except SmokeFailure as e:
            shutil.rmtree(workdir, ignore_errors=True)
            cause = e.__cause__
            if not (isinstance(cause, OSError) and cause.errno in NO_ROOM):
                raise
            # the survey asked with a sparse file; the real one was refused
            say(f"  {room['path']} refused the real file ({cause}); "
                "trying the next place")
            print(f"chip_smoke: {room} refused {sizes[depth]} bytes: {cause}",
                  file=sys.stderr, flush=True)
            continue
        nbytes = sum(
            os.path.getsize(os.path.join(root, f))
            for root, _d, files in os.walk(store) for f in files
        )
        say(f"exported 2 tenants, {size(nbytes)} of weights, in "
            f"{time.monotonic() - t0:.1f}s")
        return workdir
    raise SmokeFailure(f"every place refused the weights: {plan}")


def phase_setup(cfg: SmokeConfig) -> Setup:
    from tfservingcache_tpu import native
    from tfservingcache_tpu.models.transformer_lm import LLAMA7B_CONFIG
    from tfservingcache_tpu.ops import attention
    from tfservingcache_tpu.utils import compile_cache

    check(attention.PAGED_KERNEL_INTERPRET is False,
          "PAGED_KERNEL_INTERPRET is set: the paged kernel would run "
          "interpreted")
    say("native tier: "
        + ("C++ (libtpusc_native.so, built from native/src by make)"
           if native.native_available()
           else "pure Python (the C++ library did not build or load)"))

    # the directory `serve` will decide on by the same rule; nothing is set
    # here, it is only read so that before/after counts are of one place
    probe_dir = compile_cache.resolve()
    before = compile_cache.entry_count(probe_dir)
    say(f"compile cache: {probe_dir} "
        f"({'from ' + compile_cache.ENV_VAR if os.environ.get(compile_cache.ENV_VAR) else 'in-checkout default'}), "
        f"{before} entries before")

    model_config = dict(cfg.widths or LLAMA7B_CONFIG)
    full_depth = model_config.get("n_layers")
    say(f"model: transformer_lm d_model={model_config['d_model']} "
        f"heads={model_config['n_heads']}/{model_config['n_kv_heads']} "
        f"d_ff={model_config['d_ff']} vocab={model_config['vocab_size']} "
        f"dtype={model_config['dtype']} max_seq={model_config['max_seq']}")

    logs = LogCollector()
    logging.getLogger("tpusc").addHandler(logs)
    workdir = export_tenants(cfg, model_config, full_depth)
    return Setup(workdir, model_config, probe_dir, before, logs)


def write_config(cfg: SmokeConfig, setup: Setup, tag: str,
                 chips_per_group: int) -> tuple[str, int, int]:
    import yaml

    rest_port, grpc_port = free_port(), free_port()
    conf = {
        "serving": {
            # one resident model: tenant 1 evicts tenant 0 (the churn phase)
            "max_concurrent_models": 1,
            "hbm_capacity_bytes": 12 << 30,
            # the client's end-to-end bound covers first compiles at these
            # widths (config.py's 30 s is for toy shapes); seconds are
            # printed per request
            "load_timeout_s": cfg.request_timeout_s,
            "generate_slots": cfg.generate_slots,
            "generate_chunk_tokens": 8,
            "kv_page_tokens": cfg.kv_page_tokens,
            "kv_arena_pages": cfg.kv_arena_pages,
            "kv_paged_kernel": True,
            "kv_arena_dtype": "",
        },
        "cache": {
            "base_dir": os.path.join(setup.workdir, f"cache-{tag}"),
            "disk_capacity_bytes": 64 << 30,
            "host_tier_bytes": 16 << 30,
        },
        "model_provider": {
            "type": "disk",
            "base_dir": os.path.join(setup.workdir, "store"),
        },
        "cache_node": {"rest_port": rest_port, "grpc_port": grpc_port},
        "mesh": {"chips_per_group": chips_per_group},
        "observability": {
            "flight_dir": os.path.join(setup.workdir, f"flight-{tag}"),
        },
    }
    path = os.path.join(setup.workdir, f"config-{tag}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(conf, f)
    return path, rest_port, grpc_port


# ---------------------------------------------------------------------------
# the client: what a user of the server would send
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, cfg: SmokeConfig, setup: Setup, rest_port: int,
                 grpc_port: int) -> None:
        import numpy as np

        self.cfg = cfg
        self.setup = setup
        self.base = f"http://127.0.0.1:{rest_port}"
        self.grpc_target = f"127.0.0.1:{grpc_port}"
        self.vocab = int(setup.model_config["vocab_size"])
        rng = np.random.default_rng(2026)
        self.prompts = rng.integers(
            1, self.vocab, (4, cfg.prompt_tokens)
        ).astype(np.int32)
        self.predict_ids = rng.integers(
            1, self.vocab, (cfg.predict_batch, cfg.prompt_tokens)
        ).astype(np.int32)
        self.logits: dict[str, Any] = {}      # tenant -> (B, V) f32

    # -- transport -----------------------------------------------------------
    def http(self, method: str, path: str, body: dict | None = None,
             timeout: float | None = None) -> bytes:
        req = urllib.request.Request(
            self.base + path, method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(
                req, timeout=timeout or self.cfg.request_timeout_s
            ) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} answered {e.code}: "
                f"{e.read()[:400].decode(errors='replace')}"
            ) from None

    def wait_ready(self, stop: threading.Event) -> None:
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline and not stop.is_set():
            try:
                with urllib.request.urlopen(self.base + "/healthz",
                                            timeout=2.0) as r:
                    if r.status == 200:
                        return
            except (OSError, urllib.error.URLError):
                time.sleep(0.25)
        raise SmokeFailure("the server never answered /healthz")

    def metrics(self) -> dict[str, float]:
        """Prometheus text -> {'name{labels}': value}."""
        out: dict[str, float] = {}
        text = self.http("GET", "/monitoring/prometheus/metrics",
                         timeout=30.0).decode()
        for line in text.splitlines():
            if line and not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                try:
                    out[key] = float(val)
                except ValueError:
                    pass
        return out

    @staticmethod
    def metric_sum(metrics: dict[str, float], name: str,
                   label: str = "") -> float:
        return sum(
            v for k, v in metrics.items()
            if (k == name or k.startswith(name + "{")) and label in k
        )

    def rest_predict(self, tenant: str, ids) -> Any:
        import numpy as np

        t0 = time.monotonic()
        raw = self.http("POST", f"/v1/models/{tenant}/versions/1:predict", {
            "inputs": {"input_ids": ids.tolist()},
            "output_encoding": "base64",
        })
        out = json.loads(raw)["outputs"]
        arr = np.frombuffer(
            base64.b64decode(out["b64"]), dtype=np.dtype(out["dtype"])
        ).reshape(out["shape"])
        say(f"REST :predict {tenant} {tuple(ids.shape)} -> {arr.shape} "
            f"{arr.dtype} in {time.monotonic() - t0:.2f}s")
        return arr

    def _grpc_call(self, method: str, request):
        import grpc

        from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv

        channel = grpc.insecure_channel(
            self.grpc_target,
            options=[("grpc.max_receive_message_length", 64 << 20)],
        )
        try:
            call = channel.unary_unary(
                f"/tensorflow.serving.PredictionService/{method}",
                request_serializer=sv.PredictRequest.SerializeToString,
                response_deserializer=sv.PredictResponse.FromString,
            )
            return call(request, timeout=self.cfg.request_timeout_s)
        finally:
            channel.close()

    def grpc_predict(self, tenant: str, ids) -> Any:
        from tfservingcache_tpu.protocol import codec
        from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv

        req = sv.PredictRequest()
        req.model_spec.name = tenant
        req.model_spec.version.value = 1
        req.inputs["input_ids"].CopyFrom(codec.numpy_to_tensorproto(ids))
        t0 = time.monotonic()
        resp = self._grpc_call("Predict", req)
        check(list(resp.outputs) == ["last_token_logits"],
              f"gRPC Predict outputs {list(resp.outputs)}")
        arr = codec.tensorproto_to_numpy(resp.outputs["last_token_logits"])
        say(f"gRPC Predict {tenant} -> {arr.shape} in "
            f"{time.monotonic() - t0:.2f}s")
        return arr

    def rest_generate(self, tenant: str, prompt, **extra) -> list[int]:
        body = {"input_ids": [prompt.tolist()],
                "max_new_tokens": self.cfg.max_new_tokens, **extra}
        raw = self.http("POST", f"/v1/models/{tenant}/versions/1:generate",
                        body)
        return json.loads(raw)["tokens"][0]

    def sse_generate(self, tenant: str, prompt) -> tuple[list[int], list[int]]:
        """-> (streamed tokens, the done frame's row)."""
        raw = self.http(
            "POST", f"/v1/models/{tenant}/versions/1:generate?stream=true",
            {"input_ids": [prompt.tolist()],
             "max_new_tokens": self.cfg.max_new_tokens},
        ).decode()
        streamed: list[int] = []
        done: list[int] | None = None
        for line in raw.splitlines():
            if not line.startswith("data:"):
                continue
            frame = json.loads(line[5:].strip())
            if "token" in frame:
                streamed.append(int(frame["token"]))
            elif frame.get("done"):
                done = frame["tokens"][0]
            elif "error" in frame:
                raise SmokeFailure(f"SSE error frame: {frame}")
        check(done is not None, "SSE stream ended without a done frame")
        return streamed, done

    def grpc_generate(self, tenant: str, prompt) -> list[int]:
        import numpy as np

        from tfservingcache_tpu.protocol import codec
        from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv

        req = sv.PredictRequest()
        req.model_spec.name = tenant
        req.model_spec.version.value = 1
        req.model_spec.signature_name = "generate"
        req.inputs["input_ids"].CopyFrom(
            codec.numpy_to_tensorproto(prompt[None, :])
        )
        req.inputs["max_new_tokens"].CopyFrom(codec.numpy_to_tensorproto(
            np.asarray(self.cfg.max_new_tokens, np.int32)
        ))
        resp = self._grpc_call("Predict", req)
        return codec.tensorproto_to_numpy(
            resp.outputs["tokens"]
        )[0].tolist()

    # -- phases ---------------------------------------------------------------
    def check_logits(self, arr, what: str) -> None:
        import numpy as np

        check(arr.shape == (self.cfg.predict_batch, self.vocab),
              f"{what}: shape {arr.shape}, expected "
              f"{(self.cfg.predict_batch, self.vocab)}")
        check(arr.dtype == np.float32, f"{what}: dtype {arr.dtype}")
        check(bool(np.isfinite(arr).all()), f"{what}: non-finite logits")
        check(float(arr.std()) > 0.0, f"{what}: constant logits")

    def load_span(self, tenant: str, tier: str) -> dict:
        traces = json.loads(
            self.http("GET", "/monitoring/traces?n=100", timeout=30.0)
        )["traces"]

        def find(span: dict) -> dict | None:
            attrs = span.get("attrs", {})
            if (span.get("name") == "load" and attrs.get("tier") == tier
                    and str(attrs.get("model", "")).startswith(tenant)):
                return span
            for c in span.get("children", ()):
                hit = find(c)
                if hit is not None:
                    return hit
            return None

        for tr in traces:
            hit = find(tr)
            if hit is not None:
                return hit
        raise SmokeFailure(f"no load span (tier={tier}) for {tenant} in "
                           "/monitoring/traces")

    def phase_cold_miss(self) -> None:
        import numpy as np

        t0, _ = self.setup.tenants
        rest = self.rest_predict(t0, self.predict_ids)
        self.check_logits(rest, "REST :predict")
        span = self.load_span(t0, "disk")
        stages = {c["name"]: c for c in span.get("children", ())}
        say("cold load (store -> disk cache -> HBM) %.2fs wall; stages: %s"
            % (span["duration_s"], ", ".join(
                f"{n} {c['duration_s']:.2f}s"
                + ("*" if c.get("attrs", {}).get("overlapped") else "")
                for n, c in stages.items())))
        say("  (* = ran on the compile executor, overlapped with the "
            f"transfer; overlap ratio {span['attrs'].get('cold_overlap_ratio')})")
        xfer = stages.get("device_transfer", {}).get("attrs", {})
        check(xfer.get("pipelined") is True,
              f"the load did not take the pipelined transfer: {xfer}")
        aot = (stages.get("compile_warmup", {}).get("attrs", {})
               .get("overlapped") is True
               and stages.get("transfer_sync", {}).get("attrs", {})
               .get("pinned_by") == "aot_warmup")
        say(f"AOT executable used for the warmup: {aot}")
        check(aot, "the AOT executable was not used (jit warmup instead): "
              f"{ {n: c.get('attrs') for n, c in stages.items()} }")
        m = self.metrics()
        check(self.metric_sum(m, "tpusc_reload_source_total",
                              'tier="store"') >= 1,
              "tpusc_reload_source_total{tier=store} did not count the miss")
        grpc_out = self.grpc_predict(t0, self.predict_ids)
        self.check_logits(grpc_out, "gRPC Predict")
        check(np.array_equal(rest, grpc_out),
              "gRPC and REST disagree on the same input: max abs diff "
              f"{float(np.max(np.abs(rest - grpc_out)))}")
        self.logits[t0] = rest

    def check_tokens(self, toks: list[int], what: str) -> None:
        check(len(toks) == self.cfg.max_new_tokens,
              f"{what}: {len(toks)} tokens, asked for "
              f"{self.cfg.max_new_tokens}")
        check(all(0 <= int(t) < self.vocab for t in toks),
              f"{what}: token outside the vocabulary: {toks}")

    def phase_generate(self) -> None:
        import numpy as np

        t0, _ = self.setup.tenants
        # concurrent, unseeded, greedy: the continuous engine's traffic
        say(f"6 concurrent greedy :generate requests "
            f"({self.cfg.prompt_tokens}-token prompts, "
            f"{self.cfg.max_new_tokens} new tokens; 4 REST, 1 SSE, 1 gRPC)")
        results = in_parallel({
            **{f"rest{i}": lambda i=i: self.rest_generate(t0, self.prompts[i])
               for i in range(4)},
            "sse": lambda: self.sse_generate(t0, self.prompts[1]),
            "grpc": lambda: self.grpc_generate(t0, self.prompts[2]),
        })
        for i in range(4):
            self.check_tokens(results[f"rest{i}"], f"rest{i}")
        streamed, done = results["sse"]
        self.check_tokens(done, "sse done frame")
        check(streamed == done,
              f"SSE token frames {streamed} != done frame {done}")
        check(done == results["rest1"],
              f"SSE done frame {done} != buffered body {results['rest1']}")
        self.check_tokens(results["grpc"], "grpc")
        check(results["grpc"] == results["rest2"],
              f"gRPC generate {results['grpc']} != REST {results['rest2']}")
        check(len({tuple(results[f'rest{i}']) for i in range(4)}) > 1,
              "four different prompts produced one completion")
        repeat = self.rest_generate(t0, self.prompts[0])
        check(repeat == results["rest0"],
              f"an identical request repeated differs: {repeat} vs "
              f"{results['rest0']}")

        # explicit seed: by design the solo dense path (no engine, no pages)
        t_start = time.monotonic()
        solo = self.rest_generate(t0, self.prompts[0], seed=7)
        say(f"  seeded (solo dense path): {time.monotonic() - t_start:.2f}s")
        self.check_tokens(solo, "seeded")
        same = next((i for i, (a, b) in enumerate(zip(solo, results["rest0"]))
                     if a != b), len(solo))
        say(f"  greedy agreement, solo dense path vs paged engine: first "
            f"{same} of {len(solo)} tokens equal (bf16 near-ties may part "
            "them; the kernels' parity is the reference phase's)")

        # :predict (flash forward), the engine's prefill and the solo path's
        # prefill are three compiled forms of one model: each one's first
        # greedy token must be the predicted argmax, up to a bf16 near-tie
        # (a wrong forward would land ~4 std below it)
        row = self.rest_predict(t0, np.stack([self.prompts[0]] *
                                             self.cfg.predict_batch))[0]
        std = float(row.std())
        for what, tok in (("engine", results["rest0"][0]), ("solo", solo[0])):
            gap = float(row.max() - row[tok])
            say(f"  predict-vs-generate ({what}): first token's logit is "
                f"{gap:.4f} below the predicted argmax (logit std {std:.3f})")
            check(gap <= 0.25 * std,
                  f"{what} :generate's first token is not (nearly) "
                  ":predict's argmax")

        m = self.metrics()
        used = self.metric_sum(m, "tpusc_gen_kv_pages_used")
        total = self.metric_sum(m, "tpusc_gen_kv_pages_total")
        say(f"  arena: {total:.0f} pages, {used:.0f} in use when idle")
        check(total == self.cfg.kv_arena_pages,
              f"tpusc_gen_kv_pages_total {total} != kv_arena_pages")
        check(used == 0, f"idle tpusc_gen_kv_pages_used is {used}, not 0")

    def phase_churn(self) -> None:
        import numpy as np

        t0, t1 = self.setup.tenants
        before = self.metrics()
        other = self.rest_predict(t1, self.predict_ids)
        self.check_logits(other, f"{t1} :predict")
        check(not np.array_equal(other, self.logits[t0]),
              "two tenants with different seeds answered identically")
        status = json.loads(self.http(
            "GET", f"/v1/models/{t0}/versions/1", timeout=30.0))
        say(f"  {t0} after {t1} loaded: {status}")
        again = self.rest_predict(t0, self.predict_ids)
        check(np.array_equal(again, self.logits[t0]),
              f"{t0} came back with different logits: max abs diff "
              f"{float(np.max(np.abs(again - self.logits[t0])))}")
        after = self.metrics()

        def grew(label: str) -> float:
            name = "tpusc_reload_source_total"
            return (self.metric_sum(after, name, label)
                    - self.metric_sum(before, name, label))

        say("  reload sources this phase: " + ", ".join(
            "%s=%.0f" % (t, grew('tier="%s"' % t))
            for t in ("hbm", "host", "disk", "store", "peer")))
        check(grew('tier="host"') >= 1,
              f"{t0} did not come back from the host tier")
        span = self.load_span(t0, "host")
        say(f"  host-tier promotion of {t0}: {span['duration_s']:.2f}s")

    def phase_engine_state(self) -> None:
        """The live half of no_fallback: what the server itself reports."""
        snap = json.loads(self.http(
            "GET", "/monitoring/engine?reset=0&n=512", timeout=30.0))
        steps = [s for m in snap["models"].values() for s in m["steps"]]
        engines = sorted({s.get("engine") for s in steps})
        say(f"  /monitoring/engine: {len(steps)} steps, engines {engines}")
        check(steps and engines == ["continuous"],
              f"engine steps are not all continuous: {engines}")
        used = self.metric_sum(self.metrics(), "tpusc_gen_kv_pages_used")
        check(used == 0, f"idle tpusc_gen_kv_pages_used is {used}, not 0")


# ---------------------------------------------------------------------------
# phase: four chips — what only a chip group can show
# ---------------------------------------------------------------------------

def find_runtime():
    """The live server's runtime. ``serve()`` hands out no handle, and a
    smoke is no reason to add one: look the object up."""
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime

    found = [o for o in gc.get_objects() if isinstance(o, TPUModelRuntime)
             and o.mesh is not None]
    check(len(found) == 1, f"expected one mesh runtime, found {len(found)}")
    return found[0]


def four_chip_checks(client: Client, n: int) -> None:
    from tfservingcache_tpu.types import ModelId

    t0 = client.setup.tenants[0]
    rest = client.rest_predict(t0, client.predict_ids)
    client.check_logits(rest, "mesh :predict")
    span = client.load_span(t0, "disk")
    xfer = next(c for c in span["children"]
                if c["name"] == "device_transfer")["attrs"]
    say(f"  cold load on the {n}-chip group: {span['duration_s']:.2f}s; "
        f"transfer attrs {xfer}")
    check(xfer.get("pipelined") is True and xfer.get("sharded") is True,
          f"the load did not take the pipelined sharded path: {xfer}")

    results = in_parallel({
        f"mesh rest{i}": lambda i=i: client.rest_generate(t0, client.prompts[i])
        for i in range(4)
    })
    for name, toks in results.items():
        client.check_tokens(toks, name)
    say("  4 concurrent :generate requests answered 200")

    rt = find_runtime()
    mid = ModelId(t0, 1)
    check(rt.mesh_lockstep is False, "mesh runtime is lockstep")
    loaded = rt._resident.get(mid, touch=False)
    wq = loaded.params["layers"][0]["attn"]["wq"]
    check(len(wq.sharding.device_set) == n,
          f"wq sits on {len(wq.sharding.device_set)} devices, not {n}")
    state = rt._slot_states[mid]
    check(state.kernel is False,
          "the mesh arena should run with the Pallas kernel off")
    arrays = [state.k, state.v]
    shard_devs = {s.device for a in arrays for s in a.addressable_shards}
    check(len(shard_devs) == n,
          f"the arena's shards sit on {len(shard_devs)} devices, not {n}")
    shard_bytes = sum(int(s.data.nbytes) for a in arrays
                      for s in a.addressable_shards)
    m = client.metrics()
    gauge = client.metric_sum(m, "tpusc_gen_kv_arena_bytes")
    say(f"  weights on {len(wq.sharding.device_set)} devices "
        f"({wq.sharding.spec}); arena shards on {len(shard_devs)} devices, "
        f"{shard_bytes} bytes; tpusc_gen_kv_arena_bytes {gauge:.0f}")
    check(gauge == shard_bytes,
          f"tpusc_gen_kv_arena_bytes {gauge} != shard bytes {shard_bytes}")
    snap = json.loads(client.http(
        "GET", "/monitoring/engine?reset=0&n=512", timeout=30.0))
    say(f"  /monitoring/engine mesh stamp: {snap.get('mesh')}")
    check(snap.get("mesh", {}).get("mesh_devices") == n
          and snap["mesh"].get("mesh_fast_path") is True,
          f"mesh stamp wrong: {snap.get('mesh')}")
    engines = sorted({s.get("engine") for mm in snap["models"].values()
                      for s in mm["steps"]})
    check(engines == ["continuous"], f"mesh engine steps: {engines}")
    say("  fact, not fallback: on a mesh the Pallas paged kernel is off by "
        "design (ROADMAP S7); decode took the gather+einsum reference")


# ---------------------------------------------------------------------------
# running a server with a client beside it
# ---------------------------------------------------------------------------

def serve_with_client(config_path: str, client: Client,
                      phases: Phases,
                      steps: list[tuple[str, Callable[[], None]]]) -> int:
    """Main thread: the CLI's ``serve``. Client thread: wait for the
    sockets, run ``steps`` as phases (after the first FAIL the rest are
    recorded as not run), then SIGTERM this process — the signal ``serve()``
    itself handles. -> the CLI's return code."""
    from tfservingcache_tpu.cli import main as cli_main

    stop = threading.Event()

    def drive() -> None:
        try:
            client.wait_ready(stop)
            broken = None
            for name, fn in steps:
                if broken is None:
                    if phases.run(name, fn) is None:
                        broken = name
                else:
                    phases.skip(name, f"{broken} failed")
        except BaseException:  # noqa: BLE001 - reported, then shutdown
            traceback.print_exc()
            phases.skip("client", "the client thread died")
        finally:
            if not stop.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    driver = threading.Thread(target=drive, name="smoke-client")
    driver.start()
    try:
        rc = cli_main(["--config", config_path, "serve"])
    finally:
        stop.set()
        driver.join(timeout=60.0)
    check(not driver.is_alive(), "the client thread did not finish")
    return rc


def phase_no_fallback(cfg: SmokeConfig, setup: Setup, tally: dict) -> None:
    from tfservingcache_tpu.ops import attention

    check(attention.PAGED_KERNEL_INTERPRET is False,
          "PAGED_KERNEL_INTERPRET was switched on during the run")
    say("attention dispatch record (gate, branch, reason): traces")
    for key, n in sorted(tally.items()):
        say(f"  {key}: {n}")
    if cfg.require_kernels:
        check(tally.get(("attention", "kernel", "flash"), 0) >= 1,
              "the flash kernel was never traced")
        check(tally.get(("paged_attention", "kernel", "pallas"), 0) >= 1,
              "the paged decode kernel was never traced")
        hidden = [k for k in tally if k[1] == "reference" and (
            k[0] != "attention" or not k[2].startswith("seq="))]
        check(not hidden,
              f"a reference branch was traced for another reason than a "
              f"short sequence: {hidden}")
    # any warning on the happy path is a degradation someone chose to keep
    # (AOT -> jit warmup, promote -> full load, precompile skipped, ...):
    # the request still answers 200, so the smoke is where it must show.
    # Three warnings are facts of this run, not degradations of the device
    # path: the idle chips of a multi-chip host under chips_per_group=1, the
    # flight recorder's slow-request dumps (a first compile takes longer
    # than its 1 s bar), and a host without a C++ toolchain (setup printed
    # which tier serves).
    allowed = re.compile(
        r"local devices stay idle|flight recorder dumped slo_breach"
        r"|native tier did not"
    )
    warned = [(name, msg) for lvl, name, msg in setup.logs.records
              if lvl >= logging.WARNING]
    bad = [(name, msg) for name, msg in warned if not allowed.search(msg)]
    say(f"server log: {len(setup.logs.records)} tpusc records, "
        f"{len(warned) - len(bad)} expected warnings, {len(bad)} others")
    for name, msg in bad:
        say(f"  {name}: {msg}")
    check(not bad, "the server logged warnings on the happy path")


def phase_shutdown(rc: int | None) -> None:
    check(rc == 0, f"`tpuserve serve` returned {rc}")
    deadline = time.monotonic() + 20.0
    while True:
        alive = [t for t in threading.enumerate()
                 if t is not threading.main_thread() and t.is_alive()
                 and not t.name.startswith("smoke-")]
        blocking = [t for t in alive if not t.daemon]
        ours = [t for t in alive if t.name.startswith("tpusc-")]
        if not (blocking or ours) or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    say(f"threads alive after shutdown: {len(alive)} "
        f"({sorted(t.name for t in alive)})")
    check(not blocking, "non-daemon threads would keep the interpreter "
          f"from exiting: {[t.name for t in blocking]}")
    check(not ours, f"tpusc threads outlived close(): "
          f"{[t.name for t in ours]}")


def phase_reference(cfg: SmokeConfig, setup: Setup) -> None:
    """The repo's own yardstick, at this model's attention shapes, on this
    device: each dispatch gate against its jnp reference on a small input."""
    import jax.numpy as jnp
    import numpy as np

    from tfservingcache_tpu.ops import attention as att

    mc = setup.model_config
    hq, hkv = int(mc["n_heads"]), int(mc["n_kv_heads"])
    d = int(mc["d_model"]) // hq
    rng = np.random.default_rng(5)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    s = cfg.prompt_tokens
    q, k, v = rand(1, hq, s, d), rand(1, hkv, s, d), rand(1, hkv, s, d)
    out = att.attention(q, k, v, causal=True).astype(jnp.float32)
    ref = att.attention_reference(q, k, v, causal=True).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    say(f"attention() vs attention_reference at {(1, hq, s, d)}: "
        f"max abs err {err:.4f}")
    check(np.isfinite(err) and err < 3e-2, "attention gate disagrees")

    lanes, pt, pps = 4, cfg.kv_page_tokens, 8
    n_pages = lanes * pps + 1
    kp, vp = rand(n_pages, hkv, pt, d), rand(n_pages, hkv, pt, d)
    qd = rand(lanes, hq, 1, d)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(lanes, pps), jnp.int32)
    pos = jnp.asarray(rng.integers(0, pps * pt, lanes), jnp.int32)
    out = att.paged_attention(qd, kp, vp, tables, pos, pt)
    ref = att.paged_decode_attention(qd, kp, vp, tables, pos, pt)
    err = float(jnp.max(jnp.abs(out - ref)))
    say(f"paged_attention() vs gather+einsum at lanes={lanes} hq={hq} "
        f"hkv={hkv} d={d} pt={pt}: max abs err {err:.4f}")
    check(np.isfinite(err) and err < 3e-2, "paged attention gate disagrees")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(cfg: SmokeConfig) -> int:
    phases = Phases()
    faulthandler.enable()

    def abort() -> None:
        say(f"chip_smoke: no end after {cfg.deadline_s:.0f}s — giving up")
        faulthandler.dump_traceback(all_threads=True)
        os._exit(4)

    watchdog = threading.Timer(cfg.deadline_s, abort)
    watchdog.name = "smoke-watchdog"
    watchdog.daemon = True
    watchdog.start()

    device = phases.run("device", phase_device, cfg)
    if device is None:
        say("chip_smoke: FAILED (device)")
        return 2
    setup = phases.run("setup", phase_setup, cfg)
    if setup is None:
        say("chip_smoke: FAILED (setup)")
        return 2
    try:
        _serve_phases(cfg, setup, phases, device)
        phases.run("compile_cache", phase_compile_cache, setup)
    finally:
        logging.getLogger("tpusc").removeHandler(setup.logs)
        shutil.rmtree(setup.workdir, ignore_errors=True)
        watchdog.cancel()
    if not phases.ok:
        say(f"chip_smoke: FAILED ({', '.join(phases.failed())})")
        return 1
    say("chip_smoke: every phase OK")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _serve_phases(cfg: SmokeConfig, setup: Setup, phases: Phases,
                  device: dict) -> None:
    from tfservingcache_tpu.ops import attention

    path, rest_port, grpc_port = write_config(cfg, setup, "one", 1)
    client = Client(cfg, setup, rest_port, grpc_port)
    rc = phases.run("serve", serve_with_client, path, client, phases, [
        ("cold_miss", client.phase_cold_miss),
        ("generate", client.phase_generate),
        ("churn", client.phase_churn),
        ("engine", client.phase_engine_state),
    ])
    # snapshot before the chip-group server adds its own (by-design) traces
    tally = attention.dispatch_tally()
    phases.run("no_fallback", phase_no_fallback, cfg, setup, tally)
    phases.run("shutdown", phase_shutdown, rc)
    phases.run("reference", phase_reference, cfg, setup)

    n = device["count"]
    if n < 4:
        say(f"four_chips: not run — this host has {n} device(s), the phase "
            "needs 4")
        return
    gc.collect()  # the first server's weights and arena leave HBM
    path, rest_port, grpc_port = write_config(cfg, setup, "four", 4)
    mesh_client = Client(cfg, setup, rest_port, grpc_port)
    rc4 = phases.run("serve_four", serve_with_client, path, mesh_client,
                     phases, [
        ("four_chips", lambda: four_chip_checks(mesh_client, 4)),
    ])
    say("traced for the chip group (kernels are single-chip by design):")
    for key, count in sorted(attention.dispatch_tally().items()):
        if count > tally.get(key, 0):
            say(f"  {key}: {count - tally.get(key, 0)}")
    phases.run("four_chips_shutdown", phase_shutdown, rc4)


def phase_compile_cache(setup: Setup) -> None:
    import jax

    from tfservingcache_tpu.utils import compile_cache

    in_effect = jax.config.jax_compilation_cache_dir
    after = compile_cache.entry_count(setup.cache_dir)
    say(f"compile cache: {setup.cache_dir}: {setup.cache_entries_before} "
        f"entries before, {after} after "
        f"({after - setup.cache_entries_before} added)")
    check(in_effect == setup.cache_dir,
          f"JAX's cache dir is {in_effect!r}, expected {setup.cache_dir!r}")
    check(after > 0, "the persistent compile cache is empty after the run")


def main() -> int:
    return run(SmokeConfig())


if __name__ == "__main__":
    sys.exit(main())
