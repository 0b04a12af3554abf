"""CPU sanity for the chained on-device timer (utils/benchtime.py).

The real evidence for this harness is on hardware (tools/tpu_kernel_check.py);
here we pin two properties: (1) the estimate must separate a heavy fn from a
light one, and (2) no timed call may reuse an (executable, inputs) pair the
warmup already executed.
"""

import jax
import jax.numpy as jnp
import pytest

from tfservingcache_tpu.utils.benchtime import chained_device_time


def test_heavy_fn_times_slower_than_light():
    heavy_x = jnp.ones((384, 384), jnp.float32)
    light_x = jnp.ones((8,), jnp.float32)

    def heavy(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 1e-3 + x
        return x

    t_heavy = chained_device_time(heavy, (heavy_x,), iters=8)
    t_light = chained_device_time(lambda x: x + 1.0, (light_x,), iters=8)
    assert t_heavy > 0 and t_light > 0
    assert t_heavy > t_light, (t_heavy, t_light)


@pytest.mark.parametrize(
    "base",
    [1.0, 100.0],  # 100.0: float32 spacing ~7.6e-6 — an absolute eps-step
    # would round away and replay the warmup inputs (transport-cache hole)
)
def test_timed_inputs_never_repeat_warmup_inputs(monkeypatch, base):
    # capture the concrete first-arg values of every jitted execution; the
    # timed calls must all differ from the warmup values and from each other
    seen = []
    real_jit = jax.jit

    def spy_jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def wrapper(args, n):
            seen.append(float(jnp.ravel(args[0])[0]))
            return jitted(args, n)

        return wrapper

    monkeypatch.setattr(jax, "jit", spy_jit)
    chained_device_time(
        lambda x: x * 2.0, (jnp.full((4,), base, jnp.float32),),
        iters=4, repeats=2,
    )
    # Each adaptive round issues exactly 2 warmup calls (args[0] verbatim)
    # then repeats*2 timed calls — reconstruct rounds POSITIONALLY so a
    # timed call that regressed to replay the warmup value is caught, not
    # silently reclassified as warmup (the transport-cache hole this test
    # exists to pin).
    per_round = 2 + 2 * 2  # 2 warmups + repeats(=2) * (1-iter + n-iter)
    assert len(seen) % per_round == 0, seen
    warmup_vals, timed = set(), []
    for i in range(0, len(seen), per_round):
        warmup_vals.update(seen[i:i + 2])
        timed.extend(seen[i + 2:i + per_round])
    assert len(timed) >= 4  # at least one round of repeats * (1-iter + n-iter)
    assert all(t not in warmup_vals for t in timed)
    assert len(set(timed)) == len(timed)


def test_return_valid_flag_shapes():
    """return_valid=True yields (estimate, dominated); the default stays a
    bare float so existing call sites are untouched."""
    x = jnp.ones((64, 64), jnp.float32)

    def f(x):
        return jnp.tanh(x @ x)

    est, dominated = chained_device_time(f, (x,), iters=8, return_valid=True)
    assert isinstance(est, float) and est > 0
    assert isinstance(dominated, bool)
    plain = chained_device_time(f, (x,), iters=8)
    assert isinstance(plain, float)
