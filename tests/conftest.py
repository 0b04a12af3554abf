"""Test harness: force an 8-device virtual CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (multi-node behavior is likewise
tested with in-process fakes, following the reference's DiscoveryServiceMock
strategy — pkg/taskhandler/cluster_test.go:12-49)."""

import os

# Must run before any jax backend initialization. Two modes only: tests run
# on the CPU backend with 8 virtual devices; the hardware-gated `on_tpu`
# proofs run on the chip through tools/tpu_kernel_check.py, which sets
# TPUSC_TEST_ON_TPU=1 and leaves the platform to JAX.
if os.environ.get("TPUSC_TEST_ON_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    import jax

    if jax.default_backend() != "tpu":
        # every on_tpu test would skip and pytest would exit 0: a kernel
        # check that found no chip must fail, not pass vacuously
        raise RuntimeError(
            "TPUSC_TEST_ON_TPU=1 but the JAX backend is "
            f"{jax.default_backend()!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}): no TPU, no kernel check"
        )

import asyncio  # noqa: E402
import inspect  # noqa: E402
import subprocess  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    """Build the native library ONCE, before pytest-xdist starts its workers
    (this hook runs in the controller first; a worker has ``workerinput``).
    The library is a build product, absent from a fresh checkout, and every
    worker's first ``native.load()`` runs ``make``: built here, those are
    no-ops. No toolchain: the native tests skip, as before."""
    if hasattr(config, "workerinput"):
        return
    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tfservingcache_tpu", "native")
    try:
        subprocess.run(["make", "-C", native], capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        pass


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (pytest-asyncio isn't in the
    image). Async fixtures aren't supported — tests use async context-manager
    helpers instead."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture()
def tmp_model_store(tmp_path):
    """A provider base dir with a fabricated versioned model layout
    (reference test fixture style, diskmodelprovider_test.go:13-31)."""
    store = tmp_path / "store"
    store.mkdir()
    return store
