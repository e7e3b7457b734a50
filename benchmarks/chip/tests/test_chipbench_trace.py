"""The reduction from a profiler trace to busy time, idle gaps and op totals."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import xtrace as X  # noqa: E402

MS = 1e6  # nanoseconds


def ev(name, start_ms, dur_ms):
    return X.Event(name, start_ms * MS, dur_ms * MS)


def synthetic():
    """Two rounds in a 100 ms window: ops overlap in places, one op sticks
    out of the window, and the host stages or waits in the gaps."""
    ops = [ev("fusion.1", 5, 10), ev("fusion.2", 10, 10),   # 5..20
           ev("_qblock_kernel", 30, 5), ev("fusion.1", 35, 5),  # 30..40
           ev("custom-call.3", 60, 30),                      # 60..90
           ev("fusion.9", 95, 20)]                            # 95..115
    spans = [ev("bench.window", 0, 100), ev("bench.round", 0, 50),
             ev("bench.staging", 0, 5), ev("bench.sync", 20, 28),
             ev("bench.round", 50, 50), ev("bench.staging", 50, 10),
             ev("bench.eval", 90, 5)]
    return X.Trace({"/device:TPU:0": ops}, spans)


def test_merged_clips_and_joins():
    got = X.merged([(5, 20), (10, 25), (30, 40), (-5, 2), (95, 130)], 0, 100)
    assert got == [[0, 2], [5, 25], [30, 40], [95, 100]]


def test_gaps_cover_the_rest_of_the_window():
    busy = [[5, 20], [30, 40]]
    assert X.gaps(busy, 0, 50) == [(0, 5), (20, 30), (40, 50)]
    assert X.gaps([], 0, 10) == [(0, 10)]


def test_reduce_busy_idle_and_labels():
    r = X.reduce(synthetic())
    assert r.window_s == pytest.approx(0.100)
    # busy: 5..20, 30..40, 60..90, 95..100 (the last op is clipped)
    assert r.busy_s == pytest.approx(0.015 + 0.010 + 0.030 + 0.005)
    assert r.n_devices == 1
    # gaps 0..5, 20..30, 40..60 and 90..95, each labelled by the innermost
    # span open at its midpoint (40..60 at 50: the second round's staging)
    got = [(lab, round(s * 1e3, 9)) for lab, s in r.idle_gaps]
    assert got[:2] == [("staging", 20.0), ("sync", 10.0)]
    assert sorted(got[2:]) == [("eval", 5.0), ("staging", 5.0)]


def test_reduce_op_seconds_by_name():
    r = X.reduce(synthetic())
    assert r.op_seconds["fusion.1"] == pytest.approx(0.015)
    assert r.op_seconds["fusion.9"] == pytest.approx(0.005)  # clipped
    assert r.op_seconds["_qblock_kernel"] == pytest.approx(0.005)
    assert X.top(r.op_seconds.items(), 2) == [
        ["custom-call.3", pytest.approx(0.030)],
        ["fusion.1", pytest.approx(0.015)]]


def test_busy_is_averaged_over_devices():
    t = synthetic()
    t.device_ops["/device:TPU:1"] = [ev("fusion.1", 0, 100)]
    r = X.reduce(t)
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx((0.060 + 0.100) / 2)


def test_reduce_needs_a_window():
    t = synthetic()
    t.host_spans = [s for s in t.host_spans if s.name != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        X.reduce(t)


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.round"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = X.load(X.find_xplane(str(tmp_path)))
    names = sorted(s.name for s in t.host_spans)
    assert names == ["bench.round", "bench.window"]
    lo, hi = X.window_of(t)
    assert hi > lo
    # a CPU trace has no TPU plane: nothing is busy, and nothing is made up
    r = X.reduce(t)
    assert r.n_devices == 0 and r.busy_s == 0.0


def test_op_and_kernel_names():
    assert X.short_name("%fusion.12 = f32[2]{0} fusion(f32[2]{0} %p)") == \
        "fusion.12"
    assert X.kernel_name("vmap_jit_quantize__.7") == "quantize"
    assert X.kernel_name("jit_quantize.2") == "quantize"
    assert X.kernel_name("dequant_accumulate.1") == "dequant_accumulate"
    assert X.kernel_name("fusion.3") == "fusion"
