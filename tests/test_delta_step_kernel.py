"""``ops.delta_rule.delta_step_kernel``: the delta rule's one-token step as a
Pallas kernel on the whole lane-state array, for the live lanes and no other
(ISSUE 52). Here under the Pallas interpreter at small widths, against
``delta_step`` (every row's state read and written, plain ``jax.numpy``) and
against ``_step_loop``, the form the CPU keeps; ``step_rows_on_tpu`` is the
on-chip row of ``tests/test_kda_moe_lm.py`` / ``tests/test_olmo_hybrid_lm.py``
(``tools/tpu_kernel_check.py -k "kda_rule_on_tpu or delta_rule_on_tpu"``)."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.ops import delta_rule
from tfservingcache_tpu.ops.attention import dispatch_tally

LANES, LAYERS, LAYER = 8, 3, 1
# (heads, d_k, d_v): heads of 128 columns (a unit is one head) and of 192 (a
# unit is two heads: one and a half 128-lane rows each)
WIDTHS = {"128": (4, 16, 128), "192": (4, 24, 192),
          # 32 heads in two blocks of 16 (``STEP_BLOCK_BYTES`` cut to fit one)
          "128_two_blocks": (32, 8, 128), "192_two_blocks": (32, 8, 192)}


def operands(h, d_k, d_v, channel, lanes=LANES, layers=LAYERS, seed=0):
    """A state array and one token a lane: ``q`` / ``k`` normalised, decays
    log-uniform down to 0.05 a step (a value a channel or a head), bf16
    ``q`` / ``k`` / ``v`` as a serving program hands them."""
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((lanes, h, d_k)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d_k)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((lanes, h, d_v)).astype(np.float32)
    alpha = np.exp(rng.uniform(
        np.log(0.05), 0.0, (lanes, h, d_k) if channel else (lanes, h))).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (lanes, h)).astype(np.float32)
    states = rng.standard_normal((layers, lanes, d_k, h * d_v)).astype(np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    return (jnp.asarray(states), bf(q), bf(k), bf(v), jnp.asarray(alpha),
            jnp.asarray(beta))


def live_pair(live, lanes=LANES):
    """(``took``, ``(order, count)``) for the lanes ``live``, IN THAT ORDER
    at the front of ``order`` (a shuffled one: the kernel follows it)."""
    took = np.zeros(lanes, bool)
    took[list(live)] = True
    rest = [i for i in range(lanes) if i not in live][::-1]
    return took, (jnp.asarray(list(live) + rest, jnp.int32), jnp.int32(len(live)))


def float64_step(state, q, k, v, alpha, beta):
    """The step of one layer's ``state (S, d_k, H x d_v)`` in float64 on the
    host, from the operands as the program holds them -> (``o (S, H, d_v)``,
    the state after)."""
    f64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)  # noqa: E731
    q, k, v, alpha, beta = map(f64, (q, k, v, alpha, beta))
    lanes, h, d_k = k.shape
    s = f64(state).reshape(lanes, d_k, h, -1).transpose(0, 2, 1, 3)   # (S, H, d_k, d_v)
    s = (alpha[..., None] if alpha.ndim == 3 else alpha[..., None, None]) * s
    u = beta[..., None] * (v - np.einsum("shk,shkv->shv", k, s))
    s = s + k[..., None] * u[:, :, None, :]
    o = np.einsum("shk,shkv->shv", q, s)
    return o, s.transpose(0, 2, 1, 3).reshape(lanes, d_k, -1)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel through the Pallas interpreter (trace-time: every call
    below goes through a fresh trace)."""
    monkeypatch.setattr(delta_rule, "DELTA_KERNEL_INTERPRET", True)


def through_kernel(*args, **kwargs):
    """``delta_step_live``, asserting the gate chose the kernel."""
    before = dispatch_tally().get(("delta_step_live", "kernel", "interpret"), 0)
    out = delta_rule.delta_step_live(*args, **kwargs)
    assert dispatch_tally()[("delta_step_live", "kernel", "interpret")] == before + 1
    return out


LIVE_SETS = {"0": (), "1": (6,), "3": (5, 2, 7), "4": (3, 0, 6, 1),
             "5": (7, 1, 4, 2, 0), "8": (3, 7, 0, 5, 1, 6, 2, 4)}


@pytest.mark.parametrize("decay", ["head", "channel"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("n_live", sorted(LIVE_SETS))
def test_kernel_is_the_step_for_the_live_lanes(interpreted, monkeypatch, decay,
                                               width, n_live):
    """The kernel against ``delta_step`` (and the float64 step) for a decay a
    head and a decay a channel, heads of 128 and of 192 columns (a lane in one
    block, and in two), live sets of 0, 1, 3, 4, 5 and all of 8 lanes in a
    shuffled ``order``: the live lanes'
    states and outputs agree to float32's rounding of a sum over ``d_k``; a
    lane that took nothing and every OTHER layer's slice keep their bytes;
    ``o`` is zeros for a lane that took nothing."""
    h, d_k, d_v = WIDTHS[width]
    if width.endswith("two_blocks"):
        monkeypatch.setattr(delta_rule, "STEP_BLOCK_BYTES", 4 * d_k * 16 * d_v)
        assert delta_rule._step_blocking(h, d_k, d_v)[1] == 16
    states, *token = operands(h, d_k, d_v, decay == "channel")
    took, live = live_pair(LIVE_SETS[n_live])
    o_want, s_want = delta_rule.delta_step(states[LAYER], *token, jnp.asarray(took))
    o64, s64 = float64_step(states[LAYER], *token)
    o, after = through_kernel(states, LAYER, *token, jnp.asarray(took), live)
    o, after, before = np.asarray(o), np.asarray(after), np.asarray(states)
    for layer in range(LAYERS):
        if layer != LAYER:
            assert after[layer].tobytes() == before[layer].tobytes()
    assert after[LAYER][~took].tobytes() == before[LAYER][~took].tobytes()
    assert not o[~took].any()
    np.testing.assert_allclose(after[LAYER][took], np.asarray(s_want)[took],
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(o[took], np.asarray(o_want)[took], atol=2e-6, rtol=0)
    np.testing.assert_allclose(after[LAYER][took], s64[took], atol=2e-6, rtol=0)
    np.testing.assert_allclose(o[took], o64[took], atol=2e-6, rtol=0)


@pytest.mark.parametrize("lanes,live", [(16, (13, 2, 8, 7)), (12, (11, 0, 5)),
                                        (4, (3, 1))], ids=["16", "12", "4"])
@pytest.mark.parametrize("decay", ["head", "channel"])
def test_a_lanes_rows_of_v_and_o_are_found_in_their_tile(interpreted, decay, lanes, live):
    """``v`` and ``o`` are ``(S, H x d_v)`` with the lanes along the sublanes:
    a lane's row is read from, and written into, its tile of 8 lanes' rows
    (16 lanes: two tiles, live lanes in both) or the whole array where the
    lanes are no whole tiles (12, 4)."""
    states, *token = operands(*WIDTHS["192"], decay == "channel", lanes=lanes, seed=9)
    took, pair = live_pair(live, lanes)
    o_want, s_want = delta_rule.delta_step(states[LAYER], *token, jnp.asarray(took))
    o, after = through_kernel(states, LAYER, *token, jnp.asarray(took), pair)
    assert np.asarray(after[LAYER])[~took].tobytes() == np.asarray(states[LAYER])[~took].tobytes()
    assert not np.asarray(o)[~took].any()
    np.testing.assert_allclose(np.asarray(after[LAYER])[took], np.asarray(s_want)[took],
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(o)[took], np.asarray(o_want)[took],
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("decay", ["head", "channel"])
def test_live_is_worked_out_from_took_and_none_is_every_lane(interpreted, decay):
    """``live`` None: the kernel's ``order`` / ``count`` come from ``took``;
    ``took`` None: every lane is advanced. Both as ``_step_loop`` has them."""
    states, *token = operands(*WIDTHS["128"], decay == "channel", seed=3)
    took = np.arange(LANES) % 3 == 1
    for mask in (jnp.asarray(took), None):
        o, after = through_kernel(states, LAYER, *token, mask)
        o_want, s_want = delta_rule.delta_step(states[LAYER], *token, mask)
        rows = took if mask is not None else np.ones(LANES, bool)
        np.testing.assert_allclose(np.asarray(after[LAYER]), np.asarray(s_want),
                                   atol=2e-6, rtol=0)
        np.testing.assert_allclose(np.asarray(o)[rows], np.asarray(o_want)[rows],
                                   atol=2e-6, rtol=0)


@pytest.mark.parametrize("decay", ["head", "channel"])
def test_the_loop_and_the_kernel_are_one_step_inside_a_donated_chunk(
        interpreted, monkeypatch, decay):
    """Four steps on a carried, donated array inside one program, as a decode
    chunk makes them, through the kernel and through ``_step_loop`` (the gate
    forced shut): the same states and outputs."""
    states, *token = operands(*WIDTHS["192"], decay == "channel", seed=5)
    took, live = live_pair(LIVE_SETS["3"])

    def chunk(states, *token):
        def body(_, carry):
            states, total = carry
            o, states = delta_rule.delta_step_live(
                states, LAYER, *token, jnp.asarray(took), live)
            return states, total + o
        return jax.lax.fori_loop(
            0, 4, body, (states, jnp.zeros(token[2].shape, jnp.float32)))

    got = jax.jit(chunk, donate_argnums=0)(states + 0.0, *token)
    monkeypatch.setattr(delta_rule, "_step_kernel_refusal",
                        lambda *_: "the loop, for the comparison")
    want = jax.jit(chunk, donate_argnums=0)(states + 0.0, *token)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, rtol=0)


_BODY = delta_rule._delta_step_body


def _q_before_the_write(order_ref, beta_ref, *refs, **kw):
    """``_delta_step_body`` with a mistake planted: ``q S`` is taken on the
    state BEFORE the write (the decayed state, read a second time), the state
    itself as it should be."""
    o_ref = refs[-1]

    class Unwritten:
        """Every write strength 0: nothing is written."""
        def __getitem__(self, at):
            return beta_ref[at] * 0.0

    _BODY(order_ref, Unwritten(), *refs, **kw)
    early = o_ref[...]
    _BODY(order_ref, beta_ref, *refs, **kw)
    o_ref[...] = early


@pytest.mark.parametrize("mistake", ["reads_twice", "advances_dead"])
def test_a_mistake_in_the_kernel_fails_the_comparison(interpreted, monkeypatch, mistake):
    """The comparison of ``test_kernel_is_the_step_for_the_live_lanes`` is
    not vacuous: an output taken from the state before the write fails it,
    and so does a dead lane advanced (a ``count`` of every lane)."""
    states, *token = operands(*WIDTHS["128"], True, seed=7)
    took, (order, count) = live_pair(LIVE_SETS["3"])
    if mistake == "advances_dead":
        count = jnp.int32(LANES)
    else:
        monkeypatch.setattr(delta_rule, "_delta_step_body", _q_before_the_write)
    o_want, _ = delta_rule.delta_step(states[LAYER], *token, jnp.asarray(took))
    delta_rule.delta_step_kernel.clear_cache()      # the body is read at trace time
    try:
        o, after = through_kernel(states, LAYER, *token, jnp.asarray(took),
                                  (order, count))
    finally:
        delta_rule.delta_step_kernel.clear_cache()
    after, before = np.asarray(after), np.asarray(states)
    with pytest.raises(AssertionError):
        assert after[LAYER][~took].tobytes() == before[LAYER][~took].tobytes()
        np.testing.assert_allclose(np.asarray(o)[took], np.asarray(o_want)[took],
                                   atol=2e-6, rtol=0)


@pytest.mark.parametrize("case,args,said", [
    ("bf16_state", (jnp.bfloat16, 4, 16, 128), "not float32"),
    ("odd_192", (jnp.float32, 3, 24, 192), "in no blocks"),
    ("d_k_12", (jnp.float32, 4, 12, 128), "no multiple of 8"),
    ("wide_head", (jnp.float32, 2, 128, 8192), "in no blocks"),
    ("blocks_of_10", (jnp.float32, 40, 128, 256), "in no blocks"),
])
def test_the_gate_refuses_by_name(interpreted, case, args, said):
    """What the kernel cannot take is refused with a reason, and the loop
    runs: a state that is not float32, heads that do not pair into whole
    128-lane rows, key rows of no whole sublane tile, a head larger than a
    block, heads whose only blocks within the bytes are no whole tiles of
    ``k`` (10 of 40)."""
    assert said in delta_rule._step_kernel_refusal(*args)


def test_the_gate_is_shut_on_the_cpu_and_open_at_the_cells_widths(monkeypatch):
    """Off the TPU the loop runs and the tally says why; with the backend a
    TPU both cells' widths pass, Solar-Open2 in two blocks of 32 heads of 128
    and Olmo-Hybrid's 30 heads of 192 in one (an even number: a unit is two)."""
    assert delta_rule._step_kernel_refusal(jnp.float32, 64, 128, 128) == "backend=cpu"
    states, *token = operands(*WIDTHS["128"], True)
    before = dispatch_tally().get(("delta_step_live", "reference", "backend=cpu"), 0)
    delta_rule.delta_step_live(states, LAYER, *token)
    assert dispatch_tally()[("delta_step_live", "reference", "backend=cpu")] == before + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule._step_kernel_refusal(jnp.float32, 64, 128, 128) is None
    assert delta_rule._step_kernel_refusal(jnp.float32, 30, 96, 192) is None
    assert delta_rule._step_blocking(64, 128, 128) == (1, 32)
    assert delta_rule._step_blocking(30, 96, 192) == (2, 30)


# -- the on-chip row (called from the two models' test files) --------------------

def step_rows_on_tpu(name, h, d_k, d_v, channel, lanes, layers, floor_us):
    """At a cell's widths on the chip, ``delta_step_live`` through the kernel
    beside today's loop (the gate forced shut) at 1, 3, 4, 5 and 8 live lanes
    of ``lanes``, layer 1 of a ``layers``-layer array: the largest error of
    ``o`` and of the state against the float64 step, every other slice bit
    for bit, and us a live lane a layer (64 steps chained on the carried,
    donated array inside one program, as a decode chunk makes them) against
    ``floor_us``, what the state's bytes allow; with NO live lane the whole
    array keeps its bytes. The kernel is held to the loop's errors and to
    less than its time."""
    states, *token = operands(h, d_k, d_v, channel, lanes=lanes, layers=layers, seed=12)
    o64, s64 = float64_step(states[1], *token)
    before = np.asarray(states)
    trips = 64

    def build():
        step = jax.jit(delta_rule.delta_step_live, static_argnums=1, donate_argnums=0)

        @functools.partial(jax.jit, donate_argnums=0)
        def many(states, q, *rest):
            def body(_, carry):
                states, moved = carry
                o, states = delta_rule.delta_step_live(
                    states, 1, q + moved.astype(q.dtype), *rest)
                return states, 1e-6 * o[1, 0, 0]
            return jax.lax.fori_loop(0, trips, body, (states, jnp.float32(0)))
        return step, many

    refusal = delta_rule._step_kernel_refusal
    assert refusal(states.dtype, h, d_k, d_v) is None
    shut = lambda *_: "the loop, for the comparison"  # noqa: E731
    found = {}
    try:
        for form, gate in (("kernel", refusal), ("loop", shut)):
            delta_rule._step_kernel_refusal = gate      # read when a form is traced
            step, many = build()
            # no live lane (a chunk whose streams all ended): nothing moves
            took, pair = live_pair((), lanes)
            o, after = step(states + 0.0, 1, *token, jnp.asarray(took), pair)
            assert np.asarray(after).tobytes() == before.tobytes()
            assert not np.asarray(o).any()
            for n_live in (1, 3, 4, 5, 8):
                rng = np.random.default_rng(n_live)
                live = tuple(int(i) for i in rng.permutation(lanes)[:n_live])
                took, pair = live_pair(live, lanes)
                args = (*token, jnp.asarray(took), pair)
                o, after = step(states + 0.0, 1, *args)
                o, after = np.asarray(o), np.asarray(after)
                for layer in range(layers):
                    if layer != 1:
                        assert after[layer].tobytes() == before[layer].tobytes()
                assert after[1][~took].tobytes() == before[1][~took].tobytes()
                assert not o[~took].any()
                err_o = float(np.max(np.abs(o[took] - o64[took])))
                err_s = float(np.max(np.abs(after[1][took] - s64[took])))
                jax.block_until_ready(many(states + 0.0, *args))
                best = np.inf
                for _ in range(3):
                    fresh = jax.block_until_ready(states + 0.0)
                    t0 = time.perf_counter()
                    jax.block_until_ready(many(fresh, *args))
                    best = min(best, time.perf_counter() - t0)
                us = 1e6 * best / trips
                found[form, n_live] = (err_o, err_s, us)
                print(f"delta_step_live[{name}] {form}: {n_live} live of {lanes} lanes "
                      f"{us:.1f} us a layer = {us / n_live:.1f} us a live lane "
                      f"(the bytes allow {floor_us}); out err {err_o:.3e}, "
                      f"state err {err_s:.3e} against the float64 step", flush=True)
    finally:
        delta_rule._step_kernel_refusal = refusal
    for n_live in (1, 3, 4, 5, 8):
        (k_o, k_s, k_us), (l_o, l_s, l_us) = found["kernel", n_live], found["loop", n_live]
        assert k_o <= 1.1 * l_o + 1e-7 and k_s <= 1.1 * l_s + 1e-7, found
        assert k_us < l_us, found

