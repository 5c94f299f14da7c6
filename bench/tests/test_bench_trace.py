"""Trace reduction (``benchlib/trace.py``) on a trace built by hand and on
one the profiler writes on the CPU."""
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import trace as T                          # noqa: E402

PALLAS = 'custom_call_target="tpu_custom_call"'


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def host():
    return plane("/host:CPU", python=[
        ev("bench.setup", -500, -10),
        ev(T.WINDOW_SPAN, 0, 1000),
        ev("bench.iteration", 0, 420),
        ev("PjitFunction(step)", 10, 30),
        ev("bench.iteration", 420, 880),
        ev("PjitFunction(multiply)", 380, 410),
        ev("bench.sync", 880, 1000)])


def device(i, mods, ops=()):
    return plane(f"/device:TPU:{i}", XLA_Modules=mods, XLA_Ops=list(ops))


def test_busy_kernels_self_time_and_gaps():
    ops = [ev("%while.3 = (f32[4]) while(%p)", 100, 300),
           ev("%fusion.1 = f32[4] fusion(%a)", 120, 200),
           ev(f"%gae_norm.1 = (f32[16,8]) custom-call(%r), {PALLAS}",
              210, 260),
           ev("%add.2 = f32[4] add(%a, %b)", 500, 800)]
    red = T.reduce_planes([host(), device(0, [ev("jit_step(1)", 100, 300),
                                              ev("jit_step(1)", 500, 800)],
                                          ops)])
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(500e-9)
    by = {o.name: o for o in red.ops}
    assert by["while.3"].self_seconds == pytest.approx(70e-9)
    assert by["while.3"].module == "jit_step"
    assert by["gae_norm.1"].pallas and not by["fusion.1"].pallas
    secs, calls = red.kernel_seconds(
        lambda o: o.pallas and o.name.startswith("gae_norm"))
    assert (secs, calls) == (pytest.approx(50e-9), 1)
    assert red.top_ops(2)[0] == ["jit_step/add.2", pytest.approx(300e-9)]
    # gaps [0,100], [300,500], [800,1000], longest first, named after the
    # innermost bench span and host event over their midpoints
    assert red.top_gaps() == [
        ["bench.iteration>PjitFunction(multiply)", pytest.approx(200e-9)],
        ["bench.sync", pytest.approx(200e-9)],
        ["bench.iteration", pytest.approx(100e-9)]]


def test_busy_is_averaged_over_chips_and_clipped_to_the_window():
    red = T.reduce_planes([host(),
                           device(0, [ev("jit_a(1)", -50, 400)]),
                           device(1, [ev("jit_a(1)", 0, 200),
                                      ev("jit_b(2)", 150, 1200)])], chips=2)
    assert red.busy_s == pytest.approx((400 + 1000) / 2 * 1e-9)


def test_missing_window_or_chip_is_an_error():
    with pytest.raises(ValueError, match="host span"):
        T.reduce_planes([device(0, [])])
    with pytest.raises(ValueError, match="TPU planes"):
        T.reduce_planes([host(), device(0, [])], chips=2)


def test_cpu_profile_is_read(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.iteration"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    from jax.profiler import ProfileData
    spans = T._host_spans(list(ProfileData.from_file(path).planes))
    names = [n for _, _, n in spans]
    assert names.count("bench.iteration") == 2
    assert T.WINDOW_SPAN in names
    with pytest.raises(ValueError, match="0 TPU planes"):
        T.reduce_trace(path)        # no device plane on the CPU: no reading
