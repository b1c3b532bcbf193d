"""``bench.trace``: the interval arithmetic, the reduction on a made-up
trace, on a trace recorded here on the CPU, and the peak table."""
import time

import pytest

from . import _paths  # noqa: F401
from .. import harness
from .. import trace as tr


def test_union_and_gaps():
    busy = tr.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tr.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert tr.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_op_name():
    assert tr.op_name("%fusion.102 = pred[7000000]{0:T(1024)} fusion("
                      "pred[1000000]{0} %p), kind=kLoop") \
        == "fusion.102 fusion pred[7000000]"
    assert tr.op_name("%while.40 = (u32[8]{0:T(1024)}, s32[]{:T(128)}) "
                      "while((u32[8]{0}, s32[]) %t)") == "while.40 while tuple"
    assert tr.op_name("copy") == "copy"


def made_up_trace():
    ms = 1_000_000
    return {
        "/host:CPU": {"python": [("bench.window", 0, 100 * ms),
                                 ("bench.call", 10 * ms, 90 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": [("gather.1", 20 * ms, 50 * ms),
                        ("fusion.2", 40 * ms, 60 * ms),
                        ("copy", 95 * ms, 120 * ms)],
            "XLA Modules": [("jit_fp(12)", 20 * ms, 60 * ms)]},
    }


def test_reduce_made_up_trace():
    ms = 1e-3
    r = tr.reduce(made_up_trace(),
                  host=[("api.mis2", 5_000_000, 30_000_000)])
    assert r["window_s"] == pytest.approx(100 * ms)
    assert r["busy_s"] == pytest.approx(45 * ms)      # 20-60 and 95-100
    assert r["devices"] == 1
    assert r["device_ops"][0] == ("gather.1", pytest.approx(30 * ms))
    assert dict(r["device_ops"])["copy"] == pytest.approx(5 * ms)
    assert r["modules"] == {"jit_fp": pytest.approx(40 * ms)}
    idle = dict(r["idle_gaps"])
    # 0-20: midpoint 10 ms lies in the program span api.mis2 (5-30)
    assert idle["api.mis2"] == pytest.approx(20 * ms)
    # 60-95: one gap, labelled by its midpoint, inside bench.call
    assert idle == {"api.mis2": pytest.approx(20 * ms),
                    "bench.call": pytest.approx(35 * ms)}


def test_reduce_needs_window():
    with pytest.raises(ValueError):
        tr.reduce({"/host:CPU": {"python": []}})


def test_reduce_cpu_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call"):
            for _ in range(3):
                f(x).block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    planes = tr.load(tmp_path)
    work = {ev[0] for lines in planes.values() for evs in lines.values()
            for ev in evs if ev[0].startswith(("dot", "sine", "reduce",
                                               "fusion"))}
    assert work, "no XLA op events in the CPU trace"

    def cpu_ops(plane, line):     # the CPU client's threads stand in
        return "XLA" in line and "Eigen" not in line

    r = tr.reduce(planes, device_lines=cpu_ops)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] >= 0.05
    assert any(name in work for name, _ in r["device_ops"])
    idle = dict(r["idle_gaps"])
    assert idle.get("bench.window", 0) >= 0.04     # the sleep after the call
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_peaks_refuse_unknown_device():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.peaks("TPU v99")
