"""``ops.delta_rule.delta_chunk_kernel`` (ISSUE 47) and its twin for a decay a
CHANNEL, ``delta_channel_chunk_kernel`` (ISSUE 50): the chunked delta rule as
ONE Pallas kernel that keeps a lane's matrix state in VMEM across its chunks,
run here through its interpreter on the CPU. A case that is the same for both
decays is one test parametrised over the decay's form (``head`` / ``channel``:
``alpha (B, T, H)`` / ``(B, T, H, d_k)``, which is what ``delta_chunked``
chooses the kernel by).

With float32 operands every product of the kernel stays float32 (what it
rounds, it rounds to its operands' dtype), so it is held to the two forms the
tree already had at their own tolerance: ``delta_step`` iterated and
``_chunked_block`` (the form every other backend and shape still takes). With
bfloat16 operands, the serving dtype, it is held to the float32 result within
what rounding the state's products to bfloat16 costs. A chunk wholly past
``real_len`` is skipped: the state after it is the state before it bit for
bit, and ``o`` before ``real_len`` does not depend on the bucket."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.ops import attention as attention_ops
from tfservingcache_tpu.ops import delta_rule

WIDE = (30, 96, 192)        # Olmo-Hybrid-7B's heads
SMALL = (6, 32, 64)         # three pairs
SOLAR = (64, 128, 128)      # Solar-Open2's heads
FORMS = ["head", "channel"]


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(delta_rule, "DELTA_KERNEL_INTERPRET", True)


def _operands(seed, b, t, widths, dtype=np.float32, form="head", least=0.02):
    h, d_k, d_v = widths
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, t, h, d_k)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d_k)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, d_v)).astype(np.float32)
    alpha = rng.uniform(least, 0.999, (b, t, h) + (
        (d_k,) if form == "channel" else ())).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, h)).astype(np.float32)
    s0 = rng.standard_normal((b, d_k, h * d_v)).astype(np.float32)
    q, k, v = (jnp.asarray(a, dtype) for a in (q, k, v))
    return jnp.asarray(s0), q, k, v, jnp.asarray(alpha), jnp.asarray(beta)


def _kernel(*operands):
    # a jit of its own: the gate is read when the program is traced
    return jax.jit(lambda *a: delta_rule.delta_chunked(*a))(*operands)


def _block_form(monkeypatch, *operands):
    """``_chunked_block`` / ``_chunked_block_channel``, by the decay's form."""
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "_kernel_refusal", lambda *a: "the reference")
        return jax.jit(lambda *a: delta_rule.delta_chunked(*a))(*operands)


def _iterated(s0, q, k, v, alpha, beta, real):
    """``delta_step`` over the tokens, a row taking its first ``real``."""
    def one(s, x):
        i, q_t, k_t, v_t, a_t, b_t = x
        o, s = delta_rule.delta_step(s, q_t, k_t, v_t, a_t, b_t, i < real)
        return s, o
    t = q.shape[1]
    tokens = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta))
    s, o = jax.jit(lambda s: jax.lax.scan(one, s, (jnp.arange(t),) + tokens))(s0)
    return jnp.moveaxis(o, 0, 1), s


# (widths, T, real_len a row or None): a tail that is no whole chunk, more
# than one of the old form's blocks of 2048, real_len None / 0 / mid-chunk /
# on a chunk's edge / T, one row and two
CASES = [
    (SMALL, 64, None), (SMALL, 64, (0, 17)), (SMALL, 64, (64,)),
    (SMALL, 200, (128, 200)), (SMALL, 200, (137, 0)), (SMALL, 200, None),
    (SMALL, 2048, (1500, 2048)), (SMALL, 2048, (1984,)),
    (SMALL, 4160, (4160, 2100)), (SMALL, 4160, (64,)),
    (WIDE, 64, None), (WIDE, 200, (137, 200)), (WIDE, 2048, (1474,)),
]
# a decay a channel: the same small cases, and the wide ones at a few of
# Solar-Open2's heads of 128 x 128 (the interpreter walks every pair)
SOLAR_FEW = (4,) + SOLAR[1:]
CASES = [("head",) + case for case in CASES] + [
    ("channel",) + case for case in CASES if case[0] == SMALL] + [
    ("channel", SOLAR_FEW, 64, None), ("channel", SOLAR_FEW, 200, (137, 200)),
    ("channel", SOLAR_FEW, 1024, (700,))]


@pytest.mark.parametrize(
    "form,widths,t_len,real", CASES,
    ids=[f"{f}-{w[0]}x{w[1]}x{w[2]}-T{t}-real{'None' if r is None else '_'.join(map(str, r))}"
         for f, w, t, r in CASES])
def test_kernel_is_the_step_iterated_and_the_block_form(
        interpreted, monkeypatch, form, widths, t_len, real):
    b = 2 if real is None else len(real)
    ops = _operands(t_len + b, b, t_len, widths, form=form)
    real_arr = None if real is None else jnp.asarray(real, jnp.int32)
    counts = np.full((b,), t_len) if real is None else np.asarray(real)
    before = attention_ops.dispatch_tally().get(
        ("delta_chunked", "kernel", "interpret"), 0)
    o, s = _kernel(*ops, real_arr)
    assert attention_ops.dispatch_tally()[
        ("delta_chunked", "kernel", "interpret")] == before + 1
    o_blk, s_blk = _block_form(monkeypatch, *ops, real_arr)
    o_it, s_it = _iterated(*ops, jnp.asarray(counts, jnp.int32))
    assert o.shape == o_blk.shape == (b, t_len, widths[0], widths[2])
    np.testing.assert_allclose(s, s_blk, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s, s_it, atol=2e-5, rtol=1e-5)
    for row in range(b):
        n = int(counts[row])
        np.testing.assert_allclose(o[row, :n], o_blk[row, :n], atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(o[row, :n], o_it[row, :n], atol=2e-5, rtol=1e-5)
        if n == 0:
            assert np.asarray(s[row]).tobytes() == np.asarray(ops[0][row]).tobytes()


@pytest.mark.parametrize("form,widths", [
    ("head", SMALL), ("head", WIDE), ("channel", SMALL), ("channel", SOLAR_FEW)],
    ids=["small", "30x96x192", "channel-small", "channel-4x128x128"])
def test_bfloat16_operands_are_within_the_rounding_of_the_states_products(
        interpreted, monkeypatch, form, widths):
    """The serving dtype: W, U, Q K^T and the state go into their products as
    bfloat16 (what the matrix unit makes of a float32 ``dot``'s operands), so
    against the block form, whose float32 products the CPU keeps whole, the
    kernel differs by rounding and no more."""
    ops = _operands(3, 1, 200, widths, jnp.bfloat16, form)
    real = jnp.asarray([150], jnp.int32)
    o, s = _kernel(*ops, real)
    o_blk, s_blk = _block_form(monkeypatch, *ops, real)
    assert np.max(np.abs(o[0, :150] - o_blk[0, :150])) < 0.1 * np.std(o_blk[0, :150])
    assert np.max(np.abs(s - s_blk)) < 0.05 * np.std(s_blk)
    assert np.max(np.abs(s - s_blk)) > 0        # it did round


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("bucket", [256, 2048])
@pytest.mark.parametrize("real", [0, 64, 100, 128])
def test_a_skipped_chunk_leaves_the_state_bit_for_bit_and_o_is_the_buckets_own(
        interpreted, real, bucket, form):
    """The same ``real`` tokens in a bucket of 128 and in a larger one whose
    further tokens are other junk: the chunks past ``real_len`` are not
    computed, so the state is the same to the bit, and so is every ``o``
    before ``real_len``."""
    small = _operands(7, 1, 128, SMALL, form=form)
    junk = _operands(8, 1, bucket, SMALL, form=form)
    large = (small[0],) + tuple(
        jnp.concatenate([a, z[:, 128:]], axis=1) for a, z in zip(small[1:], junk[1:]))
    real_arr = jnp.asarray([real], jnp.int32)
    o_small, s_small = _kernel(*small, real_arr)
    o_large, s_large = _kernel(*large, real_arr)
    assert np.asarray(s_large).tobytes() == np.asarray(s_small).tobytes()
    assert np.asarray(o_large[:, :real]).tobytes() == np.asarray(o_small[:, :real]).tobytes()
    if real == 0:
        kept = np.asarray(small[0]).copy()
        kept[0, 0, 0] = -0.0
        _, s = _kernel(jnp.asarray(kept), *large[1:], real_arr)
        assert np.asarray(s).tobytes() == kept.tobytes()
    else:
        assert np.any(np.asarray(s_small) != np.asarray(small[0]))


@pytest.mark.parametrize("scale", [0.1, 0.3, 1.0])
def test_the_joined_inverse_is_float32s_own(scale):
    """``_unit_lower_inverses`` (three passes a product, a second part riding
    in the empty half of the inner dimension) against float64, beside the
    tree's ``_unit_lower_inverse``: no further from it than that one is."""
    from jax.experimental import pallas as pl

    chunk, two = 64, 128
    rng = np.random.default_rng(int(scale * 10))
    mats = []
    for _ in range(2):
        a = np.zeros((two, two), np.float32)
        for head in range(2):
            at = slice(head * chunk, (head + 1) * chunk)
            a[at, at] = np.tril(rng.standard_normal((chunk, chunk)) * scale, -1)
        mats.append(a)

    def body(a0, a1, x0, x1):
        x0[...], x1[...] = delta_rule._unit_lower_inverses([a0[...], a1[...]], chunk)

    got = pl.pallas_call(
        body, out_shape=[jax.ShapeDtypeStruct((two, two), jnp.float32)] * 2,
        interpret=True)(*map(jnp.asarray, mats))
    for a, x in zip(mats, got):
        want = np.linalg.inv(np.eye(two) + a.astype(np.float64))
        tree = np.asarray(delta_rule._unit_lower_inverse(jnp.asarray(a)))
        size = np.max(np.abs(want))
        assert np.max(np.abs(np.asarray(x) - want)) <= max(
            2 * np.max(np.abs(tree - want)), 1e-6 * size)
        # block-diagonal and unit lower triangular, exactly
        assert np.all(np.asarray(x)[:chunk, chunk:] == 0)
        assert np.all(np.triu(np.asarray(x), 1) == 0)
        assert np.all(np.diag(np.asarray(x)) == 1)


REFUSED = {
    "the_cpu": (dict(), WIDE, 64, jnp.bfloat16, "backend=cpu"),
    "float32_operands_on_the_chip": (
        dict(backend="tpu"), WIDE, 64, jnp.float32, "not bfloat16"),
    "heads_not_in_pairs": (dict(backend="tpu"), (5, 32, 64), 64, jnp.bfloat16,
                           "not in pairs"),
    "a_pair_of_value_heads_no_whole_rows": (
        dict(backend="tpu"), (4, 32, 32), 64, jnp.bfloat16, "d_v=32"),
    "a_key_width_of_no_whole_tiles": (
        dict(backend="tpu"), (4, 24, 64), 64, jnp.bfloat16, "d_k=24"),
    "a_chunk_that_is_no_power_of_two": (
        dict(backend="tpu"), WIDE, 48, jnp.bfloat16, "chunk=48"),
    "a_state_past_the_vmem_budget": (
        dict(backend="tpu"), (64, 256, 256), 64, jnp.bfloat16, "VMEM budget"),
}


# a decay a CHANNEL (ISSUE 50: refused by name until then) is given its own
# kernel at the kernel's widths; what is refused is what does not fit: at 96
# heads of 128 x 128 the state five times is within half the kernel's VMEM,
# but a chunk's operands (a float32 log-decay as wide as the keys among them)
# beside it are not. The same operands with a decay a head pass.
REFUSED["a_decay_a_channel_past_the_vmem_budget"] = (
    dict(backend="tpu", channel=True), (96, 128, 128), 64, jnp.bfloat16,
    "a decay a channel) past the VMEM budget")


def _gate_operands(widths, dtype):
    h, d_k, d_v = widths
    struct = jax.ShapeDtypeStruct
    return (struct((1, d_k, h * d_v), jnp.float32),
            struct((1, 128, h, d_k), dtype), struct((1, 128, h, d_v), dtype))


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_gate_is_what_the_code_can_see(monkeypatch, case):
    patch, widths, chunk, dtype, why = REFUSED[case]
    if "backend" in patch:
        monkeypatch.setattr(jax, "default_backend", lambda: patch["backend"])
    operands = _gate_operands(widths, dtype)
    got = delta_rule._kernel_refusal(*operands, chunk,
                                     *([True] if patch.get("channel") else []))
    assert got is not None and why in got, got
    if patch.get("channel"):       # the same operands with a decay a head pass
        assert delta_rule._kernel_refusal(*operands, chunk) is None
    else:                          # and what a head's decay is refused, a channel's is
        assert why in delta_rule._kernel_refusal(*operands, chunk, True)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("widths", [WIDE, (4, 32, 64), (16, 128, 128), SOLAR],
                         ids=["30x96x192", "4x32x64", "16x128x128", "64x128x128"])
def test_the_chip_is_given_the_kernel(monkeypatch, widths, form):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule._kernel_refusal(
        *_gate_operands(widths, jnp.bfloat16), delta_rule.CHUNK,
        form == "channel") is None


@pytest.mark.parametrize("widths,why", [
    ((5, 32, 64), "heads=5 not in pairs"), ((4, 24, 64), "d_k=24"),
    ((96, 128, 128), "a decay a channel) past the VMEM budget")],
    ids=["heads_not_in_pairs", "a_key_width_of_no_whole_tiles", "past_the_vmem_budget"])
def test_a_decay_a_channel_at_other_widths_takes_the_block_form_and_the_tally_says_why(
        monkeypatch, widths, why):
    """On the chip (as the gate sees it) with bfloat16 operands: the program
    traced holds no kernel, and ``dispatch_tally()`` names the reason."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    h, d_k, _ = widths
    struct = jax.ShapeDtypeStruct
    state, k, v = _gate_operands(widths, jnp.bfloat16)
    operands = (state, k, k, v, struct((1, 128, h, d_k), jnp.float32),
                struct((1, 128, h), jnp.float32))
    count = lambda: sum(n for key, n in attention_ops.dispatch_tally().items()  # noqa: E731
                        if key[:2] == ("delta_chunked", "reference") and why in key[2])
    before = count()
    text = str(jax.make_jaxpr(lambda *a: delta_rule.delta_chunked(*a))(*operands))
    assert "pallas_call" not in text
    assert count() == before + 1


@pytest.mark.parametrize("case", ["strong", "at_the_floor", "gone_and_back"])
def test_a_channels_strong_decays_give_no_overflow_and_no_nan(
        interpreted, monkeypatch, case):
    """Decays down to 0.05 a step (``exp(-G)`` of a chunk overflows float32),
    decays that underflowed to 0 (``log`` = -inf, floored at
    ``LOG_DECAY_FLOOR``: the running sum reaches -5120) and channels that are
    wiped and written again, with ``beta`` up to 2 (``kda_allow_neg_eigval``):
    every exponent the kernel forms is at most 0, so nothing overflows, and
    it is the block form's and the step's result."""
    widths, t_len = SMALL, 200
    s0, q, k, v, alpha, beta = _operands(5, 2, t_len, widths, form="channel")
    rng = np.random.default_rng(6)
    shape = alpha.shape
    if case == "strong":
        alpha = np.exp(rng.uniform(np.log(0.05), 0.0, shape)).astype(np.float32)
        assert float(np.sum(np.log(alpha[0, :64, 0, 0]))) < -88
    elif case == "at_the_floor":
        alpha = np.where(rng.uniform(size=shape) < 0.5, 0.0, np.asarray(alpha))
    else:
        alpha = np.where(rng.uniform(size=shape) < 0.05, 0.0, 1.0)
    alpha = jnp.asarray(alpha, jnp.float32)
    beta = jnp.where(jnp.arange(t_len)[None, :, None] % 3 == 0, 2.0, beta)
    real = jnp.asarray([t_len, 130], jnp.int32)
    operands = (s0, q, k, v, alpha, beta)
    o, s = _kernel(*operands, real)
    assert np.isfinite(np.asarray(s)).all()
    assert all(np.isfinite(np.asarray(o[row, :n])).all()       # past it: junk
               for row, n in enumerate((t_len, 130)))
    o_blk, s_blk = _block_form(monkeypatch, *operands, real)
    o_it, s_it = _iterated(*operands, real)
    # at the floor the running sums reach thousands and ``G_t - G_i`` loses
    # float32's last bits in BOTH chunked forms (the step multiplies by 0
    # where they multiply by e^-80): ten times the other cases' room
    atol = 2e-4 if case == "at_the_floor" else 2e-5
    np.testing.assert_allclose(s, s_blk, atol=atol, rtol=1e-5)
    np.testing.assert_allclose(s, s_it, atol=atol, rtol=1e-5)
    for row, n in enumerate((t_len, 130)):
        np.testing.assert_allclose(o[row, :n], o_blk[row, :n], atol=atol, rtol=1e-5)
        np.testing.assert_allclose(o[row, :n], o_it[row, :n], atol=atol, rtol=1e-5)


def test_no_exponent_the_channel_form_takes_is_above_0(monkeypatch):
    """``_advance_pairs_channel`` on one pair with decays at the floor, every
    ``exp`` it traces watched: all arguments <= 0 (the running sums of two
    rows that should be equal may differ in the last bit: they are clamped)."""
    seen = []
    real_exp = jnp.exp

    def watched(x):
        jax.debug.callback(lambda v: seen.append(float(np.max(v))), x)
        return real_exp(x)

    monkeypatch.setattr(jnp, "exp", watched)
    chunk, d_k, d_v = 64, 32, 64
    rng = np.random.default_rng(2)
    two = 2 * chunk
    k2, q2 = (jnp.asarray(rng.standard_normal((two, d_k)) / np.sqrt(d_k), jnp.float32)
              for _ in "kq")
    lg2 = np.log(rng.uniform(1e-3, 1.0, (two, d_k)))
    lg2[rng.uniform(size=lg2.shape) < 0.3] = delta_rule.LOG_DECAY_FLOOR
    lg2[rng.uniform(size=lg2.shape) < 0.3] = -1e-7
    beta = rng.uniform(0, 2, (two,)).astype(np.float32)
    from jax.experimental import pallas as pl

    def body(k_r, q_r, lg_r, v_r, row_r, col_r, s0_r, s1_r, o_r, t0_r, t1_r):
        o, s = delta_rule._advance_pairs_channel.__wrapped__(
            (k_r[...],), (q_r[...],), (lg_r[...],), (v_r[...],), (row_r[...],),
            (col_r[...],), ((s0_r[...], s1_r[...]),))
        o_r[...], (t0_r[...], t1_r[...]) = o[0], s[0]

    f32 = jnp.float32
    out = pl.pallas_call(
        body, out_shape=[jax.ShapeDtypeStruct((two, d_v), f32)]
        + [jax.ShapeDtypeStruct((d_k, d_v), f32)] * 2, interpret=True)(
        k2, q2, jnp.asarray(lg2, f32), jnp.asarray(rng.standard_normal((two, d_v)), f32),
        jnp.asarray(beta)[None, :], jnp.asarray(beta)[:, None],
        *(jnp.asarray(rng.standard_normal((d_k, d_v)), f32) for _ in range(2)))
    jax.block_until_ready(out)
    assert all(np.isfinite(np.asarray(a)).all() for a in out)
    assert len(seen) >= delta_rule.SUB and max(seen) <= 0.0, max(seen)


@pytest.mark.parametrize("form", FORMS)
def test_the_cpu_takes_the_block_form_and_says_so(form):
    ops = _operands(1, 1, 70, SMALL, form=form)
    before = attention_ops.dispatch_tally().get(
        ("delta_chunked", "reference", "backend=cpu"), 0)
    text = str(jax.make_jaxpr(lambda *a: delta_rule.delta_chunked(*a))(*ops))
    assert "pallas_call" not in text and "scan" in text
    assert attention_ops.dispatch_tally()[
        ("delta_chunked", "reference", "backend=cpu")] == before + 1


# -- what the TPU compiler makes of it, without a chip ---------------------------

_COMPILE_ONLY_ENV = {
    # what libtpu asks its environment when no TPU VM metadata answers
    "TPU_SKIP_MDS_QUERY": "1", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost", "TPU_LOG_DIR": "disabled",
    "JAX_PLATFORMS": "cpu",
}


def compile_for_a_described_v5e(t_len: int = 1024, form: str = "head"):
    """A child process's call: ``delta_chunked`` at the cell's widths (a decay
    a head: Olmo-Hybrid's; a decay a channel: Solar-Open2's) compiled
    by XLA:TPU and Mosaic for one chip of a v5e that libtpu describes with
    none attached. Prints COMPILED with the program's temporaries, or
    NO_TOPOLOGY."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu", chip_config_name="default",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:  # noqa: BLE001 - reported; the parent skips
        print("NO_TOPOLOGY", type(e).__name__, e)
        return
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"               # the gate asks this
    chip = SingleDeviceSharding(topo.devices[0])
    h, d_k, d_v = SOLAR if form == "channel" else WIDE
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=chip)
    compiled = jax.jit(lambda *a: delta_rule.delta_chunked(*a)).trace(
        struct((1, d_k, h * d_v), jnp.float32),
        struct((1, t_len, h, d_k), jnp.bfloat16),
        struct((1, t_len, h, d_k), jnp.bfloat16),
        struct((1, t_len, h, d_v), jnp.bfloat16),
        struct((1, t_len, h) + ((d_k,) if form == "channel" else ()), jnp.float32),
        struct((1, t_len, h), jnp.float32),
        struct((1,), jnp.int32)).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    name = "delta_channel_chunk_kernel" if form == "channel" else "delta_chunk_kernel"
    print("COMPILED", "kernel" if name in text else "NO KERNEL",
          "temp_bytes", compiled.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("form,t_len", [("head", 1024), ("channel", 512),
                                         ("channel", 16384)])
def test_the_kernel_compiles_for_a_described_v5e(form, t_len):
    """Mosaic takes the kernel at the cell's widths (a decay a head: heads
    whose 96 key columns are cut out of 128-lane rows, a transposed left
    operand, rolls of float32 tiles; a decay a channel: 64 heads of 128 x 128,
    the state 4.2 MB, sub-blocks cut out of the sublanes and summed over the
    lanes) inside ``VMEM_LIMIT``, and the program around it holds no float32
    temporaries of a block's size: the 0.3 GB the block form kept are gone."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(_COMPILE_ONLY_ENV)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'tests'); import test_delta_chunk_kernel as t;"
         f" t.compile_for_a_described_v5e({t_len}, {form!r})"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    if "NO_TOPOLOGY" in r.stdout:
        pytest.skip("libtpu describes no v5e here: " + r.stdout.strip()[:200])
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("COMPILED")]
    assert line, (r.stdout[-2000:], r.stderr[-4000:])
    assert line[0].split()[1] == "kernel"
    # around the channel kernel the program holds the float32 log-decay it
    # hands in and ``o`` as the caller's ``(T, H, d_v)``: a few times the
    # decay's own size, which an array a (chunk, chunk) pair would pass
    most = 64 << 20 if form == "head" else max(64 << 20, 4 * t_len * 64 * 128 * 4)
    assert int(line[0].split()[-1]) < most, line
