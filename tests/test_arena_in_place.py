"""The paged steps write their new KV rows into the arena in place (S10).

Structural, on the traced programs at a toy size: no equation of the decode
chunk or of a prefill chunk takes a layer's slice out of the 5-D arena, copies
one, or stacks slices back. Before PR 26 ``_paged_forward_step`` did all three
once a layer (``cache["k"][li]``, ``.at[page, :, off].set`` on the slice,
``jnp.stack``): eight passes over 268 MB to write 128 KB in the chat cells,
73 % of the chip's busy time. What the jaxpr may hold now:

- no ``slice`` / ``dynamic_slice`` / ``squeeze`` / ``concatenate`` /
  ``dynamic_update_slice`` / ``broadcast_in_dim`` whose result is a layer's
  slice of an arena buffer, or has as many elements as one or more;
- arena-shaped results only from ``scatter`` (``_paged_write_rows``), one a
  layer for each of ``k``, ``v`` and, on an int8 arena, the two scale buffers
  (the programs that carry the arena, ``pjit``, ``scan`` and ``while``, pass
  it through). Since PR 32 a decode chunk's write takes the rows of its live
  lanes: a loop a layer over groups of ``_WRITE_GROUP`` lanes, the arena its
  carry, each trip the same scatters on fewer lanes. The walk descends into
  the loop's body; a prefill chunk writes every row through no loop.

The toy arena has far more pages than lanes x pages a lane, so what the CPU
reference legitimately gathers (the lanes' own pages) is small beside a layer.

One case more compiles the decode chunk for a v5e that libtpu describes with
no chip attached: what the TPU compiler makes of the form is the finding of
PR 26 (a careless scatter brought arena-sized layout copies), so the compiled
program holds no layer- or arena-sized result but the scatters', and its
temporaries stay those of the program that writes every lane. It runs for a
head-128 decoder and, since PR 34, for the LFM2 cell's attention (8 KV heads
of 64, stored two a 128-lane row): the kernel is in the program and the arena
is row-major wherever it appears. Since PR 39 it also runs for a model with
WINDOW layers: both arenas are donated and carried, neither is copied, both
kernels are in the program, and that model's fresh prefill holds the windowed
flash kernel and no score block. Since PR 42 the same holds for an ADMISSION:
the insert programs write whole pages, so their compiled form is the in-place
write alone (until then a row was the unit, and four copies of the arena
stood around every insert).
"""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.extend
import jax.numpy as jnp
import pytest

import tfservingcache_tpu.models.generation as generation
from tfservingcache_tpu.models.registry import build

LANES, PPS, PT, N_PAGES, CHUNK = 8, 4, 4, 320, 2
DENSE = ("transformer_lm", {
    "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64})
EXPERT = ("moe_lm", {
    "vocab_size": 97, "d_model": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 4, "d_ff": 32, "n_experts": 8, "top_k": 2,
    "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
    "max_seq": 64, "rope_theta": 10000.0, "dtype": "float32"})
MOVERS = {"slice", "dynamic_slice", "squeeze", "concatenate",
          "dynamic_update_slice", "broadcast_in_dim"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            item = getattr(item, "jaxpr", item)          # a ClosedJaxpr's own
            if isinstance(item, jax.extend.core.Jaxpr):
                yield item


def _equations(jaxpr):
    """Every equation, with those of nested programs (pjit, scan, cond)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


def _program(which, family, cfg, cache):
    """(the jitted program with its static arguments bound, abstract operands)"""
    params = jax.eval_shape(build(family, cfg).init, jax.random.PRNGKey(0))
    scales = ({"k": cache["k_scale"], "v": cache["v_scale"]}
              if "k_scale" in cache else None)
    static = dict(cfg_key=tuple(sorted(cfg.items())), family=family,
                  page_tokens=PT, kernel=False)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    lane = i32((LANES,))
    if which == "decode_chunk":
        fn = functools.partial(generation._paged_decode_chunk_jit,
                               chunk=CHUNK, **static)
        args = (params, cache["k"], cache["v"], scales, i32((LANES, PPS)),
                lane, lane, jax.ShapeDtypeStruct((LANES,), jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.uint32),
                jax.ShapeDtypeStruct((LANES,), jnp.float32), lane)
    else:
        fn = functools.partial(generation._paged_prefill_chunk_jit, **static)
        args = (params, cache["k"], cache["v"], scales, i32((1, PPS)),
                i32((1, 8)), i32((1,)), i32((1,)))
    return fn, args


@pytest.mark.parametrize("which", ["decode_chunk", "prefill_chunk"])
@pytest.mark.parametrize("model", [DENSE, EXPERT], ids=["dense", "expert"])
@pytest.mark.parametrize("arena_dtype", ["", "int8"], ids=["bf16", "int8"])
def test_no_layer_slice_leaves_the_arena_and_none_is_stacked_back(
        arena_dtype, model, which):
    family, config = model
    cfg = build(family, config).config
    cache = jax.eval_shape(
        lambda: generation.init_paged_cache(cfg, N_PAGES, PT, arena_dtype))
    fn, args = _program(which, family, cfg, cache)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr

    buffers = {(b.shape, b.dtype) for b in cache.values()}
    layer_slices = {b.shape[1:] for b in cache.values()}
    layer_elems = cache["k"].size // cfg["n_layers"]
    assert LANES * PPS * 8 < N_PAGES          # the reference's gather is small
    scatters = {key: 0 for key in buffers}
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        for out in eqn.outvars:
            aval = out.aval
            if not hasattr(aval, "shape"):
                continue
            if name in MOVERS:
                assert aval.size < layer_elems, (name, aval)
                assert aval.shape not in layer_slices, (name, aval)
                assert aval.shape[1:] not in layer_slices or aval.shape[0] != 1, (
                    name, aval)
            if (aval.shape, aval.dtype) in buffers:
                if name == "scatter":
                    scatters[(aval.shape, aval.dtype)] += 1
                else:
                    # only a program that carries the arena may hand it on
                    assert list(_sub_jaxprs(eqn)), (name, aval)
    # one scatter a layer for k and for v (the same shape and dtype), and on
    # an int8 arena as many again for their scales; in a decode chunk they sit
    # in the body of the layer's loop over the live lanes
    assert LANES > generation._WRITE_GROUP
    assert scatters == {key: 2 * cfg["n_layers"] for key in buffers}, scatters
    loops = sum(eqn.primitive.name == "while" for eqn in _equations(jaxpr))
    assert loops == (cfg["n_layers"] if which == "decode_chunk" else 0)
    assert len(buffers) == (2 if arena_dtype == "int8" else 1)


# -- what the TPU compiler makes of it, without a chip -------------------------

_COMPILE_ONLY_ENV = {
    # what libtpu asks its environment when no TPU VM metadata answers
    "TPU_SKIP_MDS_QUERY": "1",
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_LOG_DIR": "disabled",
    "JAX_PLATFORMS": "cpu",
}
# results that only hand a buffer on
_CARRIERS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "conditional", "call", "optimization-barrier"}


def _large_results(hlo: str, elems: int) -> dict:
    """{operation: count} over the instructions of a compiled module whose
    result has at least ``elems`` elements; a fusion is named by the root of
    the computation it calls (``fusion:scatter``)."""
    roots = {}
    current = None
    for line in hlo.splitlines():
        head = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = head.group(1)
        root = re.match(r"\s*ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if root and current:
            roots[current] = root.group(1)
    found = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([0-9,]*)\]\S* ([\w\-]+)\(",
                     line)
        if not m or m.group(3) in _CARRIERS:
            continue
        size = 1
        for dim in filter(None, m.group(2).split(",")):
            size *= int(dim)
        if size < elems:
            continue
        op = m.group(3)
        if op == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", line)
            op = "fusion:" + roots.get(called.group(1) if called else "", "?")
        found[op] = found.get(op, 0) + 1
    return found


def _compile_only_models():
    """{case: (family, config, lanes' pages)} of the compile-only test. ``dense``
    is a head-128 decoder (the chat cells' arena); ``hybrid_head64`` has the
    LFM2 cell's attention (``lfm2-longgen-steady``: hidden 2048, 32 / 8 heads of
    64, gated convolutions beside it, experts behind two dense layers; the
    experts small, they are not what is compiled here) over the cell's arena of
    8193 pages, which 32 lanes of 4096 positions address whole."""
    return {
        "dense": ("transformer_lm", {
            "vocab_size": 4096, "d_model": 1024, "n_layers": 2, "n_heads": 8,
            "n_kv_heads": 8, "d_ff": 2048, "max_seq": 2048,
            "dtype": "bfloat16"}, 1025),
        "hybrid_head64": ("hybrid_lm", {
            "vocab_size": 4096, "d_model": 2048, "n_layers": 4,
            "layer_types": ["conv", "conv", "full_attention", "full_attention"],
            "conv_kernel": 3, "n_heads": 32, "n_kv_heads": 8,
            "n_dense_layers": 2, "d_ff_dense": 1024, "d_ff": 256,
            "n_experts": 8, "top_k": 4, "norm_topk_prob": True,
            "route_score": "sigmoid", "route_norm_eps": 1e-6, "max_seq": 4096,
            "rope_theta": 1e6, "dtype": "bfloat16"}, 8193),
        # the Mellum2 cell's attention (``mellum2-codectx-mixed``: 4 KV heads
        # of 128 that the hidden size does not give, three window layers of
        # 1024 to a global one, a rotary a kind) over the cell's arenas: 16385
        # pages for the global layer, 32 lanes x 65 pages a window layer
        "window": ("moe_lm", {
            "vocab_size": 4096, "d_model": 1024, "n_layers": 4, "n_heads": 8,
            "n_kv_heads": 4, "head_dim": 128, "d_ff": 256, "n_experts": 8,
            "top_k": 2, "norm_topk_prob": True, "tie_embeddings": False,
            "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
            "sliding_window": 1024, "max_seq": 8192, "rope_theta": 5e5,
            "rope_full": {"yarn": 16.0, "original_max": 8192, "beta_fast": 32.0,
                          "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
            "rms_eps": 1e-6, "dtype": "bfloat16"}, 16385),
    }


def _described_v5e():
    """One chip of a v5e that libtpu describes with none attached, as a
    sharding for abstract operands (a child process's call: it loads the
    library); prints NO_TOPOLOGY and returns None where it cannot."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu",
            chip_config_name="default", chips_per_host_bounds=(2, 2, 1),
            num_slices=1)
    except Exception as e:  # noqa: BLE001 - reported; the parent skips
        print("NO_TOPOLOGY", type(e).__name__, e)
        return None
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _layouts(hlo: str, arenas) -> list:
    """The layouts a compiled module gives the arenas' shapes, wherever they
    appear: ``["{4,3,2,1,0"]`` is row-major everywhere."""
    found = set()
    for arena in arenas:
        found |= set(re.findall(
            r"\[%s\](\{[0-9,]*)" % ",".join(map(str, arena.shape)), hlo))
    return sorted(found)


def _child(call: str):
    """``test_arena_in_place.<call>`` in a process of its own, set up to
    compile for a chip it does not have -> (stdout, stderr)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(_COMPILE_ONLY_ENV)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'tests'); import test_arena_in_place;"
         f" test_arena_in_place.{call}"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    return r.stdout, r.stderr


def _compile_for_v5e_main(case="dense"):
    """Child-process body of the test below: the decode chunk of ``case``'s
    model at 32 lanes over an arena far larger than anything else it touches,
    compiled for a described v5e, as the tree writes it and with every lane's
    rows written (the parent's write: no order handed down). Prints what it
    found, or NO_TOPOLOGY."""
    from tfservingcache_tpu.models.registry import static_config

    one = _described_v5e()
    if one is None:
        return
    # the gates ask the backend, "cpu" in this process: answer for the chip
    jax.default_backend = lambda: "tpu"
    family, config, n_pages = _compile_only_models()[case]
    lanes, pt, chunk = 32, 16, 8
    md = build(family, config)
    cfg = dict(static_config(md))
    cache = jax.eval_shape(
        lambda: generation.init_paged_cache(cfg, n_pages, pt, row=md.cache_row,
                                            lanes=lanes))
    ring = (cache["wk"], cache["wv"]) if "wk" in cache else None
    s = jax.ShapeDtypeStruct
    # weights as a server holds them: in the model's dtype
    params = jax.tree_util.tree_map(
        lambda a: s(a.shape, jnp.bfloat16),
        jax.eval_shape(md.init, jax.random.PRNGKey(0)))
    lane = s((lanes,), jnp.int32)
    args = (params, cache["k"], cache["v"], None,
            s((lanes, cfg["max_seq"] // pt), jnp.int32), lane, lane,
            s((lanes,), jnp.bool_), s((), jnp.uint32),
            s((lanes,), jnp.float32), lane,
            jax.eval_shape(lambda: generation.init_lane_state(cfg, lanes)), ring)
    args = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype, sharding=one), args)
    # a layer of the SMALLER arena: a copy of either shows
    layer_elems = min(a.size // a.shape[0] for a in (cache["k"], *(ring or ())))
    live_lanes = generation._live_lanes
    for name, form in (("live", live_lanes), ("every", lambda active: None)):
        generation._live_lanes = form
        generation._paged_decode_chunk_jit.clear_cache()
        compiled = generation._paged_decode_chunk_jit.lower(
            *args, cfg_key=static_config(md), family=family,
            chunk=chunk, page_tokens=pt, kernel=True).compile()
        hlo = compiled.as_text()
        if ring:
            assert "paged_window_decode_kernel" in hlo, "no window kernel"
        print("COMPILED", name,
              "temp", compiled.memory_analysis().temp_size_in_bytes,
              "kernel", int("paged_decode_attention_kernel" in hlo),
              "loops", len(re.findall(r" while\(", hlo)),
              "large", json.dumps(_large_results(hlo, layer_elems)),
              "layouts", json.dumps(_layouts(hlo, (cache["k"], *(ring or ())[:1]))))
    print("LAYER_BYTES", layer_elems * 2, "ARENA", json.dumps(cache["k"].shape))
    if ring:
        # the same model's fresh prefill of 2048 tokens: through the attention
        # gate in every layer, so no score block
        tokens = 2048
        args = (params, s((1, tokens), jnp.int32), s((1,), jnp.int32),
                s((2,), jnp.uint32), s((), jnp.float32), s((), jnp.int32))
        args = jax.tree_util.tree_map(
            lambda a: s(a.shape, a.dtype, sharding=one), args)
        compiled = generation._slot_prefill_jit.lower(
            *args, cfg_key=static_config(md), family=family).compile()
        hlo = compiled.as_text()
        print("PREFILL temp", compiled.memory_analysis().temp_size_in_bytes,
              "window_kernels", len(re.findall(r"flash_window_kernel", hlo)),
              "score_block", cfg["n_heads"] * tokens * tokens * 4)


@pytest.mark.parametrize("case", ["dense", "hybrid_head64", "window"])
def test_decode_chunk_compiled_for_v5e_holds_no_arena_sized_copy(case):
    """The decode chunk compiled for a v5e, off the chip: every layer- or
    arena-sized result is a scatter's (in place on the donated arena), inside
    the write's loops too: no ``copy``, no ``transpose``, no layout conversion
    around a loop; and the program's temporaries are those of the program that
    writes every lane, but for the gathered rows (far under one layer of the
    arena). Skipped where libtpu cannot describe the topology.

    ``hybrid_head64`` (PR 34): the LFM2 cell's attention, 8 KV heads of 64.
    Its arena is stored two heads a 128-lane row, so the paged kernel is in
    the program (the parent's gate refused a head of 64), the device keeps the
    arena row-major wherever it appears (the parent's ``(…, 8, 16, 64)`` arena
    was kept with the PAGES minor and converted whole, in and out, every
    chunk), and the temporaries are far under one layer of the arena (the
    parent's gather of every table slot of every lane was a layer a side:
    2.2 GB over the cell's 3 layers).

    ``window`` (PR 39): a model with window layers. Both arenas ride in
    donated and come out of scatters alone (no copy of a layer of EITHER),
    both are row-major wherever they appear, the window call's kernel is in
    the program beside the global one's, and the same model's fresh prefill
    of 2048 tokens holds the windowed flash kernel in its window layers and
    temporaries far under one ``(heads, S, S)`` float32 score block."""
    out, err = _child(f"_compile_for_v5e_main({case!r})")
    if "NO_TOPOLOGY" in out:
        pytest.skip("libtpu compile-only topology unavailable: "
                    + out.strip()[-300:])
    found = dict(re.findall(r"COMPILED (\w+) (.*)", out))
    assert set(found) == {"live", "every"}, (out[-3000:], err[-3000:])
    temp, loops = {}, {}
    sizes = re.search(r"LAYER_BYTES (\d+) ARENA (.*)", out)
    layer_bytes, arena = int(sizes.group(1)), json.loads(sizes.group(2))
    for name, line in found.items():
        m = re.match(
            r"temp (\d+) kernel (\d) loops (\d+) large (.*) layouts (.*)", line)
        temp[name] = int(m.group(1))
        assert m.group(2) == "1", ("the paged kernel was not traced", line)
        large = json.loads(m.group(4))
        assert large and set(large) <= {"scatter", "fusion:scatter"}, (name, large)
        loops[name] = int(m.group(3))
        # row-major wherever the compiled program names the arena's shape
        assert json.loads(m.group(5)) == ["{4,3,2,1,0"], (name, line)
    # the form under test was compiled: loops beside the chunk's scan
    assert loops["live"] > loops["every"], loops
    assert temp["live"] - temp["every"] < layer_bytes // 8, (temp, layer_bytes)
    if case == "hybrid_head64":
        assert arena == [2, 8193, 4, 16, 128]
        assert temp["live"] < layer_bytes // 4, (temp, layer_bytes)
    if case == "window":
        assert arena == [1, 16385, 4, 16, 128]           # the ONE global layer
        assert layer_bytes == 32 * 65 * 4 * 16 * 128 * 2   # a window layer's ring
        m = re.search(r"PREFILL temp (\d+) window_kernels (\d+) score_block (\d+)",
                      out)
        assert m, (out[-3000:], err[-3000:])
        assert int(m.group(2)) >= 3, m.group(0)
        assert int(m.group(1)) < int(m.group(3)) // 2, m.group(0)


# -- an admission's insert, compiled for the same v5e --------------------------

INSERT_BUCKETS = (512, 4096)


def _compile_inserts_for_v5e_main(case="dense"):
    """Child-process body of the test below: ``case``'s insert program
    (``_window_paged_insert_jit`` for the model with window layers,
    ``_paged_insert_jit`` for the others) at every bucket of
    ``INSERT_BUCKETS``, compiled for a described v5e over the arena the decode
    chunk's case has (the dense one at the Mistral cell's 4097 pages: a layer
    is then eight times the 4096 bucket's rows). Prints what it found, or
    NO_TOPOLOGY."""
    from tfservingcache_tpu.models.registry import static_config

    one = _described_v5e()
    if one is None:
        return
    family, config, n_pages = _compile_only_models()[case]
    n_pages = max(n_pages, 4097)
    lanes, pt = 32, 16
    md = build(family, config)
    cfg = dict(static_config(md))
    cache = jax.eval_shape(
        lambda: generation.init_paged_cache(cfg, n_pages, pt, row=md.cache_row,
                                            lanes=lanes))
    rings = (cache["wk"], cache["wv"]) if "wk" in cache else ()
    arenas = (cache["k"], cache["v"], *rings)
    layer_elems = min(a.size // a.shape[0] for a in arenas)
    s = jax.ShapeDtypeStruct
    i32 = s((), jnp.int32)
    for bucket in INSERT_BUCKETS:
        rows = s((generation._row_layers(cfg), 1, cfg["n_kv_heads"], bucket,
                  md.cache_row.width), jnp.bfloat16)
        table = s((max(cfg["max_seq"], bucket) // pt,), jnp.int32)
        if rings:
            fn, args, static = generation._window_paged_insert_jit, (
                *arenas, rows, rows, table, i32, i32), dict(
                window_layers=generation.window_rows(cfg),
                ring_pages=rings[0].shape[1] // lanes)
        else:
            fn, args, static = generation._paged_insert_jit, (
                *arenas, None, rows, rows, table, i32), {}
        args = jax.tree_util.tree_map(
            lambda a: s(a.shape, a.dtype, sharding=one), args)
        compiled = fn.lower(*args, page_tokens=pt, **static).compile()
        hlo = compiled.as_text()
        print("INSERT", bucket,
              "temp", compiled.memory_analysis().temp_size_in_bytes,
              "large", json.dumps(_large_results(hlo, layer_elems)),
              "layouts", json.dumps(_layouts(hlo, arenas)))
    print("LAYER_BYTES", layer_elems * 2,
          "ARENAS", json.dumps([a.shape for a in arenas[::2]]))


@functools.lru_cache(maxsize=None)
def _compiled_inserts(case):
    """The child's output for ``case``: one process compiles every bucket."""
    return _child(f"_compile_inserts_for_v5e_main({case!r})")


@pytest.mark.parametrize("bucket", INSERT_BUCKETS)
@pytest.mark.parametrize("case", ["dense", "hybrid_head64", "window"])
def test_insert_compiled_for_v5e_holds_no_arena_sized_copy(case, bucket):
    """An admission's insert compiled for a v5e, off the chip, at a 512 and a
    4096 bucket: every layer- or arena-sized result is the in-place write's
    (the page scatter on each side of the donated arena and, for the model
    with window layers, the one contiguous update of the lane's ring): no
    ``copy``, no ``transpose``, no layout conversion; every arena is row-major
    wherever its shape appears; the temporaries (one side's rows in page form,
    pages first as the TPU's scatter takes its updates: bucket x layers x a
    row's bytes) are under a quarter of one arena layer. Skipped where libtpu
    cannot describe the topology.

    What the PARENT's programs showed under this harness (a row was the
    scatter's unit, layers and KV heads in its window): FOUR arena-sized
    ``copy`` results around the two scatters in every case at both buckets
    (each side converted to a layout of the scatter's own, ``{4,2,3,1,0``, and
    back), EIGHT in ``hybrid_head64`` at the 4096 bucket (``{4,3,1,2,0``; over
    the LFM2 cell's 3-layer arena at its 1024 bucket too), and temporaries of
    one whole side of the arena whatever the bucket: 268.7 MB in each case
    here, 1.07 GB over the Mistral cell's 8 layers."""
    out, err = _compiled_inserts(case)
    if "NO_TOPOLOGY" in out:
        pytest.skip("libtpu compile-only topology unavailable: "
                    + out.strip()[-300:])
    found = dict(re.findall(r"INSERT (\d+) (.*)", out))
    assert set(found) == set(map(str, INSERT_BUCKETS)), (out[-3000:], err[-3000:])
    sizes = re.search(r"LAYER_BYTES (\d+) ARENAS (.*)", out)
    layer_bytes, arenas = int(sizes.group(1)), json.loads(sizes.group(2))
    m = re.match(r"temp (\d+) large (.*) layouts (.*)", found[str(bucket)])
    large = json.loads(m.group(2))
    writes = {"scatter", "fusion:scatter"}
    if case == "window":
        writes |= {"dynamic-update-slice", "fusion:dynamic-update-slice"}
        assert arenas == [[1, 16385, 4, 16, 128], [3, 32 * 65, 4, 16, 128]]
    if case == "hybrid_head64":
        assert arenas == [[2, 8193, 4, 16, 128]]          # packed
    assert large and set(large) <= writes, (case, bucket, large)
    assert json.loads(m.group(3)) == ["{4,3,2,1,0"], found[str(bucket)]
    assert int(m.group(1)) < layer_bytes // 4, (found[str(bucket)], layer_bytes)


# -- a lane state of two parts, 2.2 / 4.2 MB a layer a lane, compiled for the same v5e --

def _lane_state_models():
    """{case: (family, config, lanes)} of the lane-state compile test: the
    linear-attention layers of the two delta-rule cells at their published
    widths and the benchmark's lanes, the hidden size, the MLP / experts and
    the vocabulary small (they are not what is compiled here).
    ``olmo_hybrid``: 6 of 8 layers, 30 heads of 96 x 192, a decay a head: the
    state is ``(6, 16, 96, 5760)`` float32 = 212 MB and ``(6, 16, 3, 11520)``
    bf16. ``kda_moe``: Solar-Open2's one period, 3 of 4 layers, 64 heads of
    128 x 128, a decay a channel: ``(3, 32, 128, 8192)`` float32 = 403 MB."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    return {
        "olmo_hybrid": ("olmo_hybrid_lm", {
            "vocab_size": 4096, "d_model": 512, "n_layers": 8,
            "layer_types": period * 2,
            "n_heads": 4, "n_kv_heads": 4, "d_ff": 1024, "linear_heads": 30,
            "linear_key_dim": 96, "linear_value_dim": 192, "linear_conv": 4,
            "max_seq": 2048, "dtype": "bfloat16"}, 16),
        "kda_moe": ("kda_moe_lm", {
            "vocab_size": 4096, "d_model": 512, "n_layers": 4,
            "layer_types": ["full_attention"] + ["linear_attention"] * 3, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 128, "linear_heads": 64,
            "linear_key_dim": 128, "linear_value_dim": 128, "linear_conv": 4,
            "linear_gate_rank": 64, "linear_allow_neg_eigval": True, "d_ff": 128,
            "d_ff_shared": 128, "n_experts": 16, "n_experts_held": 8,
            "expert_first": 0, "top_k": 4, "norm_topk_prob": True,
            "route_score": "sigmoid", "route_scale": 1.0, "rms_eps": 1e-5,
            "rope_theta": None, "max_seq": 2048, "dtype": "bfloat16"}, 32),
    }


def _compile_lane_state_for_v5e_main(case="olmo_hybrid"):
    """Child-process body of the test below: the decode chunk of ``case``'s
    model (``_lane_state_models``) compiled for a described v5e as the tree
    writes it there (KERNEL: ``ops.delta_rule.delta_step_kernel``, the gate
    open because the backend answers "tpu") and with the gate forced shut
    (LOOP: ``_step_loop``, what every other backend runs), and for
    ``olmo_hybrid`` ``_lane_insert_jit`` too. Prints what it found, with the
    step's lines of ``dispatch_tally()``, or NO_TOPOLOGY."""
    from tfservingcache_tpu.models.registry import static_config
    from tfservingcache_tpu.ops import delta_rule
    from tfservingcache_tpu.ops.attention import dispatch_tally

    one = _described_v5e()
    if one is None:
        return
    jax.default_backend = lambda: "tpu"
    family, config, lanes = _lane_state_models()[case]
    pt, n_pages = 16, 1025
    md = build(family, config)
    cfg = dict(static_config(md))
    s = jax.ShapeDtypeStruct
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: s(a.shape, a.dtype, sharding=one), tree)
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, n_pages, pt, row=md.cache_row, lanes=lanes))
    state = jax.eval_shape(lambda: generation.init_lane_state(cfg, lanes))
    params = jax.tree_util.tree_map(
        lambda a: s(a.shape, jnp.bfloat16),
        jax.eval_shape(md.init, jax.random.PRNGKey(0)))
    lane = s((lanes,), jnp.int32)
    args = on((params, cache["k"], cache["v"], None,
               s((lanes, cfg["max_seq"] // pt), jnp.int32), lane, lane,
               s((lanes,), jnp.bool_), s((), jnp.uint32),
               s((lanes,), jnp.float32), lane, state, None))
    whole = state[0].size
    refusal = delta_rule._step_kernel_refusal
    for name, gate in (("KERNEL", refusal),
                       ("LOOP", lambda *_: "the loop, for the comparison")):
        delta_rule._step_kernel_refusal = gate
        generation._paged_decode_chunk_jit.clear_cache()
        compiled = generation._paged_decode_chunk_jit.lower(
            *args, cfg_key=static_config(md), family=family, chunk=8,
            page_tokens=pt, kernel=True).compile()
        hlo = compiled.as_text()
        steps = [line for line in hlo.splitlines()
                 if "custom-call(" in line and "delta_step_kernel" in line]
        print(name, "temp", compiled.memory_analysis().temp_size_in_bytes,
              "kernel", int("paged_decode_attention_kernel" in hlo),
              "large", json.dumps(_large_results(hlo, whole)),
              "layouts", json.dumps(_layouts(hlo, state[:1])),
              "steps", len(steps), "aliased",
              sum(bool(re.search(r"output_to_operand_aliasing=\{\{0\}: \(\d+, \{\}\)", line))
                  for line in steps))
    delta_rule._step_kernel_refusal = refusal
    print("TALLY", json.dumps(sorted(
        [*key, n] for key, n in dispatch_tally().items() if key[0] == "delta_step_live")))
    if case == "olmo_hybrid":
        new = jax.tree_util.tree_map(
            lambda a: s((a.shape[0], 1, *a.shape[2:]), a.dtype), state)
        compiled = generation._lane_insert_jit.lower(
            *on((state, new, s((), jnp.int32)))).compile()
        hlo = compiled.as_text()
        print("INSERT temp", compiled.memory_analysis().temp_size_in_bytes,
              "large", json.dumps(_large_results(hlo, whole)),
              "layouts", json.dumps(_layouts(hlo, state[:1])))
    print("STATE_BYTES", whole * 4, "LAYER_BYTES", whole * 4 // state[0].shape[0])


@functools.lru_cache(maxsize=None)
def _compiled_lane_state(case):
    return _child(f"_compile_lane_state_for_v5e_main({case!r})")


@pytest.mark.parametrize("program", ["CHUNK", "INSERT"])
def test_lane_state_of_matrix_states_compiled_for_v5e_is_held_in_place(program):
    """A lane state of Olmo-Hybrid-7B's size (two parts; the float32 part 212
    MB at 16 lanes, where phi4's is 0.10 GB and LFM2's 2.9 MB in all) rides
    donated through the decode chunk and through ``_lane_insert_jit`` and is
    written where it lies: every result as large as the state's float32 array
    is the in-place write's (in the chunk, with the step's gate shut, a
    ``dynamic-update-slice`` a lane of a trip a linear layer,
    ``ops.delta_rule._step_loop`` putting a live lane's slice back; in the
    insert a lane's slice set) and nothing
    else: no ``copy``, no ``transpose``, no layout conversion, no gather or
    scatter that the compiler serves through a copy of the array, no layer's
    slice set whole; the array is row-major wherever its shape appears; the
    chunk's temporaries, the whole program's, are under two layers' slices
    of the state and the insert's are next to nothing. Skipped where libtpu
    cannot describe the topology."""
    out, err = _compiled_lane_state("olmo_hybrid")
    if "NO_TOPOLOGY" in out:
        pytest.skip("libtpu compile-only topology unavailable: "
                    + out.strip()[-300:])
    name = "LOOP" if program == "CHUNK" else program
    m = re.search(name + r" temp (\d+) (?:kernel (\d) )?large (.*) layouts (\S*)", out)
    sizes = re.search(r"STATE_BYTES (\d+) LAYER_BYTES (\d+)", out)
    assert m and sizes, (out[-3000:], err[-3000:])
    state_bytes, layer_bytes = int(sizes.group(1)), int(sizes.group(2))
    assert state_bytes == 6 * 16 * 96 * 5760 * 4
    large = json.loads(m.group(3))
    assert json.loads(m.group(4)) == ["{3,2,1,0"], m.group(0)
    if program == "CHUNK":
        # a trip's four lanes put back, a dynamic update a lane a linear layer
        assert large == {"dynamic-update-slice": 24,
                         "fusion:dynamic-update-slice": 24}, large
        assert m.group(2) == "1", "the paged kernel was not traced"
        assert int(m.group(1)) < 2 * layer_bytes, m.group(0)
    else:
        assert large and set(large) <= {
            "dynamic-update-slice", "fusion:dynamic-update-slice"}, large
        assert int(m.group(1)) < layer_bytes // 16, m.group(0)


@pytest.mark.parametrize("case", ["olmo_hybrid", "kda_moe"])
def test_decode_chunk_compiled_for_v5e_holds_the_step_kernel_in_place(case):
    """With the step's gate open (the backend a TPU) the decode chunk of both
    delta-rule models advances its matrix states through
    ``delta_step_kernel``, one custom call a linear layer whose first output
    IS its state operand (``output_to_operand_aliasing``), and NOTHING else
    in the compiled program produces a result as large as the state's array:
    no ``copy``, no gather, no ``dynamic-update-slice``, no layer's slice cut
    out or set whole; the array is row-major wherever its shape appears and
    the whole program's temporaries stay under two layers' slices.
    ``dispatch_tally()`` says ``delta_step_live -> kernel`` there (a trace a
    linear layer) and ``reference``, with the reason, for the form every other
    backend takes. Skipped where libtpu cannot describe the topology."""
    out, err = _compiled_lane_state(case)
    if "NO_TOPOLOGY" in out:
        pytest.skip("libtpu compile-only topology unavailable: "
                    + out.strip()[-300:])
    m = re.search(r"KERNEL temp (\d+) kernel (\d) large (.*) layouts (\S*) "
                  r"steps (\d+) aliased (\d+)", out)
    loop = re.search(r"LOOP temp \d+ kernel \d large (.*) layouts \S* steps (\d+)", out)
    sizes = re.search(r"STATE_BYTES (\d+) LAYER_BYTES (\d+)", out)
    tally = re.search(r"TALLY (.*)", out)
    assert m and loop and sizes and tally, (out[-3000:], err[-3000:])
    layers = {"olmo_hybrid": 6, "kda_moe": 3}[case]
    assert int(sizes.group(1)) == {
        "olmo_hybrid": 6 * 16 * 96 * 5760 * 4, "kda_moe": 3 * 32 * 128 * 8192 * 4}[case]
    assert json.loads(m.group(3)) == {}, m.group(0)
    assert json.loads(m.group(4)) == ["{3,2,1,0"], m.group(0)
    assert int(m.group(5)) == int(m.group(6)) == layers, m.group(0)
    assert m.group(2) == "1", "the paged kernel was not traced"
    assert int(m.group(1)) < 2 * int(sizes.group(2)), m.group(0)
    # the loop, forced: no kernel, its dynamic updates instead
    assert int(loop.group(2)) == 0 and set(json.loads(loop.group(1))) == {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}, loop.group(0)
    assert json.loads(tally.group(1)) == [
        ["delta_step_live", "kernel", "pallas", layers],
        ["delta_step_live", "reference", "the loop, for the comparison", layers]]
