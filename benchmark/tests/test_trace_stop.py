"""``run.stop_trace``: the capture without the profiler's export, and the
public call where JAX's private session is not what it was."""

import time

import run as benchrun
import trace_reduce as tr


def test_the_capture_is_written_where_the_reduction_looks_for_it(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(f"{tr.WALL_MARK}{time.time_ns()}"):
        pass
    jnp.ones((8, 8)).sum().block_until_ready()
    benchrun.stop_trace(str(tmp_path))
    path = tr.find_xplane(str(tmp_path))
    assert path.endswith("bench.xplane.pb")
    rows = tr.load_xplane(path)
    offset = tr.wall_offset_ns(rows)
    assert offset is not None and abs(offset / 1e9 - time.time()) < 3600.0
    # the session is over: another trace can start, and the public stop ends it
    jax.profiler.start_trace(str(tmp_path / "again"))
    jax.profiler.stop_trace()


def test_without_the_private_session_the_public_call_stops_the_trace(
        tmp_path, monkeypatch):
    import jax
    from jax._src import profiler as private

    called = []
    monkeypatch.setattr(private, "_profile_state", object())
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: called.append(1))
    benchrun.stop_trace(str(tmp_path))
    assert called == [1] and not list(tmp_path.iterdir())
