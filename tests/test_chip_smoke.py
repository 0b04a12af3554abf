"""What can be known about the chip path without a chip.

- ``chip_smoke.py`` refuses to run off-TPU, a failed phase fails the run,
  and its phases work end to end at a tiny size on the CPU harness;
- the compile-cache rule (utils/compile_cache.py);
- every Pallas kernel LOWERS for the TPU (``jax.export``, the check that
  found the int8 scale-block failure) and COMPILES for a v5e (Mosaic, through
  libtpu's compile-only topology) — no device needed for either;
- the attention gates record the branch they traced.
"""

import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from tfservingcache_tpu.ops import attention as att
from tfservingcache_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _has_result_line(stdout: str) -> bool:
    return any(line.startswith('{"ok"') for line in stdout.splitlines())


# -- chip_smoke.py -------------------------------------------------------------

def test_smoke_exits_nonzero_at_the_device_phase_without_a_chip():
    r = _run([SMOKE], REPO, timeout=120)
    assert r.returncode != 0
    assert "device: FAIL" in r.stdout
    assert "platform 'cpu'" in r.stdout and "needs 'tpu'" in r.stdout
    assert "JAX_PLATFORMS=cpu is set" in r.stdout
    assert "--- setup" not in r.stdout      # stopped at the first phase
    assert not _has_result_line(r.stdout)


def test_smoke_alone_in_a_directory_fails_a_phase_and_the_run(tmp_path):
    """With nothing of the repo beside it the setup phase cannot import the
    package: a failed phase is a non-zero exit and no result line."""
    (tmp_path / "chip_smoke.py").write_text(open(SMOKE).read())
    if _run(["-c", "import tfservingcache_tpu"], str(tmp_path),
            env_extra={"PYTHONPATH": ""}).returncode == 0:
        pytest.skip("the package is installed site-wide in this environment")
    r = _run(["-c", "import sys, chip_smoke; sys.exit(chip_smoke.run("
                    "chip_smoke.SmokeConfig(platform='cpu')))"],
             str(tmp_path), env_extra={"PYTHONPATH": ""}, timeout=120)
    assert r.returncode != 0
    assert "device: OK" in r.stdout and "setup: FAIL" in r.stdout
    assert "ModuleNotFoundError" in r.stdout
    assert not _has_result_line(r.stdout)


_TINY_RUN = textwrap.dedent("""
    import sys
    import chip_smoke
    tiny = {"vocab_size": 257, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 4, "d_ff": 128, "max_seq": 256,
            "rope_theta": 10000.0, "dtype": "float32"}
    sys.exit(chip_smoke.run(chip_smoke.SmokeConfig(
        platform="cpu", widths=tiny, n_layers=2, kv_arena_pages=128,
        require_kernels=True, request_timeout_s=120, deadline_s=280)))
""")


def test_smoke_phases_run_on_the_cpu_harness_and_cannot_hide_a_fallback(
        tmp_path):
    """The whole flow — `tpuserve serve` under a client thread, REST + SSE +
    gRPC, churn through the host tier, SIGTERM shutdown, the chip-group
    server on 4 virtual devices — at a tiny size. On a CPU the references
    are what gets traced, so with kernels required exactly one phase fails,
    and that fails the run."""
    cache = tmp_path / "compile-cache"
    r = _run(["-c", _TINY_RUN], REPO, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_COMPILATION_CACHE_DIR": str(cache),
    })
    out = r.stdout
    for phase in ("device", "setup", "cold_miss", "generate", "churn",
                  "engine", "serve", "shutdown", "reference", "four_chips",
                  "four_chips_shutdown", "compile_cache"):
        assert f"\n{phase}: OK" in out, (phase, out[-3000:], r.stderr[-3000:])
    assert "no_fallback: FAIL" in out
    assert "the flash kernel was never traced" in out
    assert "('attention', 'reference', 'backend=cpu')" in out
    assert "AOT executable used for the warmup: True" in out
    assert f"compile cache: {cache} (from JAX_COMPILATION_CACHE_DIR)" in out
    assert r.returncode == 1
    assert out.rstrip().splitlines()[-1] == "chip_smoke: FAILED (no_fallback)"
    assert not _has_result_line(out)
    assert compile_cache.entry_count(str(cache)) > 0


_LIMITED_EXPORT = textwrap.dedent("""
    import glob, os, resource, shutil, sys
    import chip_smoke
    def left():
        return {d for p in ("/tmp", "/dev/shm", chip_smoke.HERE)
                for d in glob.glob(os.path.join(p, "tpusc-smoke-*"))}
    before = left()
    tiny = {"vocab_size": 257, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 4, "d_ff": 128, "max_seq": 256,
            "rope_theta": 10000.0, "dtype": "float32"}
    cfg = chip_smoke.SmokeConfig(platform="cpu", widths=tiny, n_layers=2)
    two, one = (chip_smoke.artifact_bytes(tiny, d) for d in (2, 1))
    limit = (two + one) // 2
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, limit))
    if sys.argv[1] == "blind":     # a survey that cannot see the limit
        chip_smoke.max_file_bytes = lambda directory, want: want
    workdir = chip_smoke.export_tenants(cfg, tiny, 2)
    files = sorted(os.path.getsize(os.path.join(root, f))
                   for root, _d, fs in os.walk(workdir) for f in fs
                   if f == "params.bin")
    shutil.rmtree(workdir)
    print("RESULT", tiny["n_layers"], files == [one, one], left() <= before)
""")


@pytest.mark.parametrize("survey", ["sees", "blind"])
def test_smoke_puts_the_weights_where_a_file_that_long_is_accepted(survey):
    """The driver's first chip run died in setup with EFBIG on params.bin.
    Under a file-size limit between the 2-layer and the 1-layer artifact the
    export must end at 1 layer and say why — whether the sparse-file survey
    saw the limit, or (blind) only the real write met it."""
    r = _run(["-c", _LIMITED_EXPORT, survey], REPO, timeout=120)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "RESULT 1 True True" in r.stdout, r.stdout
    assert "depth cut to 1 of 2 layers" in r.stdout
    assert "below the 2 this smoke asks for" in r.stdout
    if survey == "blind":
        assert "refused the real file" in r.stdout
        assert "File too large" in r.stderr
    else:
        assert "refused the real file" not in r.stdout


# -- the compile-cache rule ----------------------------------------------------

def test_compile_cache_dir_from_the_environment_is_left_to_jax(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append(name))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/outside")
    assert compile_cache.configure("/operator/choice") == "/somewhere/outside"
    assert "jax_compilation_cache_dir" not in calls


def test_compile_cache_dir_unset_is_operator_choice_else_fixed_in_checkout(
        monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.configure("/operator/choice") == "/operator/choice"
    assert compile_cache.configure() == compile_cache.DEFAULT_DIR
    assert [v for n, v in calls if n == "jax_compilation_cache_dir"] == [
        "/operator/choice", compile_cache.DEFAULT_DIR,
    ]
    assert compile_cache.DEFAULT_DIR == os.path.join(
        REPO, ".jax_compile_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_one_module_places_the_compile_cache():
    """Nothing else sets the directory, and nothing derives it from a
    temporary name, a pid or the time."""
    setters = []
    for root in ("tfservingcache_tpu", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            setters += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    setters += [os.path.join(REPO, f)
                for f in ("chip_smoke.py", "__graft_entry__.py")]
    hits = [p for p in setters
            if re.search(r"""update\(\s*["']jax_compilation_cache_dir""",
                         open(p).read())]
    assert hits == [os.path.join(REPO, "tfservingcache_tpu", "utils",
                                 "compile_cache.py")]
    src = open(hits[0]).read()
    assert not re.search(r"\b(tempfile|mkdtemp|getpid|time\.)", src)


# -- the dispatch record -------------------------------------------------------

def test_gates_record_the_branch_they_traced():
    before = att.dispatch_tally()

    def grew(key):
        return att.dispatch_tally().get(key, 0) - before.get(key, 0)

    q = jnp.zeros((1, 4, 128, 64), jnp.bfloat16)
    att.attention(q, q, q)
    assert grew(("attention", "reference", "backend=cpu")) == 1
    pages = jnp.zeros((3, 4, 8, 64), jnp.bfloat16)
    tables = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    qd = jnp.zeros((2, 4, 1, 64), jnp.bfloat16)
    att.paged_attention(qd, pages, pages, tables, pos, 8, kernel=False)
    assert grew(("paged_attention", "reference", "kernel=False")) == 1
    att.paged_attention_verify(qd, pages, pages, tables, pos, 8)
    assert grew(("paged_attention_verify", "reference", "backend=cpu")) == 1


def test_kernel_refusal_reasons(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert att._kernel_refusal(128, 32, 32) is None
    assert att._kernel_refusal(64, 8, 2) is None
    # the paged decode gate: its kernel copies pages out of HBM itself
    assert "128" in att._kernel_refusal(64, 8, 2, head_multiple=128)
    assert "head_dim" in att._kernel_refusal(48, 4, 4)
    assert "kv heads" in att._kernel_refusal(64, 6, 4)
    q = jax.ShapeDtypeStruct((1, 4, 128, 64), jnp.bfloat16)
    before = att.dispatch_tally()
    # traced only (eval_shape): the kernel branch cannot run on this backend
    jax.eval_shape(lambda q: att.attention(q, q, q, partitioned=True), q)
    key = ("attention", "reference",
           "partitioned program (kernel is single-chip)")
    assert att.dispatch_tally().get(key, 0) - before.get(key, 0) == 1


# -- kernels lower for the TPU -------------------------------------------------

def _export_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _paged_args(g, d, quantized, t_q, lanes=4, hkv=4, pps=8, layers=2,
                n_pages=None):
    """Operands of a paged kernel on the whole arena ``(layers, n_pages, hkv,
    16, d)``, as the model steps hand it over (read at ``layer=layers - 1``)."""
    s = jax.ShapeDtypeStruct
    pt = 16
    n_pages = n_pages or lanes * pps + 1
    q = s((lanes, hkv * g, t_q, d), jnp.bfloat16)
    arena = s((layers, n_pages, hkv, pt, d),
              jnp.int8 if quantized else jnp.bfloat16)
    args = [q, arena, arena, s((lanes, pps), jnp.int32),
            s((lanes,), jnp.int32)]
    if quantized:
        scale = s((layers, n_pages, hkv, pt), jnp.float32)
        args += [scale, scale]
    return args, pt


@pytest.mark.parametrize("variant", ["resident", "streamed", "carry"])
def test_flash_kernels_lower_for_tpu(variant, monkeypatch):
    s = jax.ShapeDtypeStruct
    q = s((2, 8, 256, 128), jnp.bfloat16)
    kv = s((2, 4, 256, 128), jnp.bfloat16)
    if variant == "carry":
        acc = s((2, 8, 256, 128), jnp.float32)
        stat = s((2, 8, 256, 1), jnp.float32)
        _export_tpu(att.flash_attention_carry, q, kv, kv, acc, stat, stat,
                    s((), jnp.int32))
        return
    if variant == "streamed":
        monkeypatch.setattr(att, "KV_RESIDENT_LIMIT_BYTES", 0)
        att.flash_attention.clear_cache()
    try:
        _export_tpu(att.flash_attention, q, kv, kv)
    finally:
        att.flash_attention.clear_cache()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("g", [1, 2, 3, 4])
@pytest.mark.parametrize("kernel", ["decode", "verify"])
def test_paged_kernels_lower_for_tpu(kernel, g, d, quantized):
    """The int8 arms failed here before this test existed: a per-head
    ``(1, 1, page_tokens)`` scale block is neither the array's own last two
    dims nor an (8, 128) multiple, which the Pallas TPU lowering refuses.
    The decode kernel refuses head 64 by name (it copies pages out of HBM
    itself, and Mosaic slices an HBM operand in whole 128-lane tiles)."""
    args, pt = _paged_args(g, d, quantized, 1 if kernel == "decode" else 5)
    fn = (att.paged_decode_attention_kernel if kernel == "decode"
          else att.paged_verify_attention_kernel)
    refused = kernel == "decode" and d % 128
    with (pytest.raises(ValueError, match="multiple of 128") if refused
          else contextlib.nullcontext()):
        _export_tpu(lambda *a: fn(*a, page_tokens=pt, layer=1), *args)


# -- kernels compile for a v5e (Mosaic), still without a device ----------------

_COMPILE_ONLY_ENV = {
    # what libtpu asks its environment when no TPU VM metadata answers
    "TPU_SKIP_MDS_QUERY": "1",
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost",
}


def _compile_only_main():
    """Child-process body of the test below: compile, for a v5e described by
    libtpu with no chip attached, every paged and flash kernel and one
    tensor-parallel ``:predict``. Prints COMPILED_ALL or the failures."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from tfservingcache_tpu.models.registry import build
    from tfservingcache_tpu.parallel.sharding import param_shardings

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu",
            chip_config_name="default", chips_per_host_bounds=(2, 2, 1),
            num_slices=1)
    except Exception as e:  # noqa: BLE001 - reported; the parent skips
        print("NO_TOPOLOGY", type(e).__name__, e)
        return
    one = SingleDeviceSharding(topo.devices[0])

    def on(sharding, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    failed = []

    def compile_(name, fn, *args):
        try:
            jax.jit(fn).lower(*args).compile()
        except Exception as e:  # noqa: BLE001 - collected and reported
            failed.append(name)
            print("FAILED", name, str(e)[:600].replace("\n", " | "))

    s = jax.ShapeDtypeStruct
    q = s((2, 8, 256, 128), jnp.bfloat16)
    kv = s((2, 4, 256, 128), jnp.bfloat16)
    compile_("flash", att.flash_attention, *on(one, (q, kv, kv)))
    for kernel, t_q in (("decode", 1), ("verify", 5)):
        fn = (att.paged_decode_attention_kernel if kernel == "decode"
              else att.paged_verify_attention_kernel)
        for g in (1, 2, 3, 4):
            # head 64: the decode kernel refuses it (ValueError, the test
            # above); the verify kernel's blocks come through the pipeline
            for d in ((128,) if kernel == "decode" else (64, 128)):
                for quantized in (False, True):
                    args, pt = _paged_args(g, d, quantized, t_q)
                    compile_(f"{kernel} g={g} d={d} int8={quantized}",
                             lambda *a: fn(*a, page_tokens=pt, layer=1),
                             *on(one, args))
    # the benchmark's steady cell: 32 lanes, 8 kv heads x group 4 of 128,
    # 128 table slots over the 8-layer, 4096-page arena read at its last
    # layer, with the chunk's active vector
    args, pt = _paged_args(4, 128, False, 1, lanes=32, hkv=8, pps=128,
                           layers=8, n_pages=4097)
    compile_("decode at the steady cell's shape",
             lambda *a: att.paged_decode_attention_kernel(
                 *a[:5], None, None, a[5], page_tokens=pt, layer=7),
             *on(one, args + [s((32,), jnp.bool_)]))
    # a tensor-parallel :predict at a flash-qualifying shape: the gate must
    # keep the bare Mosaic kernel out of the partitioned program (the TPU
    # lowering refuses to partition one). The gates ask jax.default_backend()
    # — "cpu" in this compile-only process — so answer for the chip.
    jax.default_backend = lambda: "tpu"
    mesh = Mesh(np.array(topo.devices), ("model",))
    md = build("transformer_lm", {
        "vocab_size": 512, "d_model": 256, "n_layers": 1, "n_heads": 4,
        "n_kv_heads": 4, "d_ff": 512, "max_seq": 256, "dtype": "bfloat16"})
    params = jax.eval_shape(md.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a, sh: s(a.shape, jnp.bfloat16, sharding=sh), params,
        param_shardings(params, md.partition_rules, mesh))
    ids = on(NamedSharding(mesh, PartitionSpec()),
             {"input_ids": s((2, 128), jnp.int32)})
    compile_("tp4 predict seq128", md.bind_mesh(mesh), params, ids)
    if not any(k[:2] == ("attention", "reference") and "partitioned" in k[2]
               for k in att.dispatch_tally()):
        failed.append("dispatch record")
        print("FAILED dispatch record", att.dispatch_tally())
    print("COMPILED_ALL" if not failed else "SOME_FAILED", failed)


def test_kernels_compile_for_v5e_without_a_device():
    """Lowering proves the Pallas front end accepts a kernel; Mosaic is what
    has to compile it. libtpu can describe a v5e topology and compile for it
    with no chip attached, so the block shapes, the page copies out of HBM
    (sub-tile int8 pages among them), the g < 8 query blocks and the scale
    rows all meet the real compiler here. Skipped where libtpu cannot
    describe the topology."""
    r = _run(["-c", "import sys; sys.path.insert(0, 'tests'); "
                    "import test_chip_smoke; "
                    "test_chip_smoke._compile_only_main()"],
             REPO, env_extra=_COMPILE_ONLY_ENV, timeout=600)
    if "NO_TOPOLOGY" in r.stdout:
        pytest.skip("libtpu compile-only topology unavailable: "
                    + r.stdout.strip()[-300:])
    assert "COMPILED_ALL" in r.stdout, (r.stdout[-4000:], r.stderr[-2000:])
