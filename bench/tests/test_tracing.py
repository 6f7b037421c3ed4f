"""The trace reduction: busy union, idle share and gaps on hand-made
intervals, and on a small trace recorded here."""
import time

import pytest

from bench import tracing


def _trace(intervals, window, spans=()):
    ops = [tracing.Op("op", s, e) for s, e in intervals]
    return tracing.Trace(ops={0: ops}, spans=list(spans), window=window)


def test_union_of_overlapping_intervals():
    assert tracing.union_ns([(0, 10), (5, 15), (20, 30), (29, 31), (40, 40)]) == 26
    assert tracing.union_ns([]) == 0


def test_gaps_and_idle_share():
    tr = _trace([(10, 20), (15, 30), (50, 60)], (0, 100),
                spans=[("bench/step", 0, 100), ("bench/deliver", 60, 100)])
    assert tracing.gaps([(o.start_ns, o.end_ns) for o in tr.ops[0]], tr.window) == [
        (0, 10), (30, 50), (60, 100)]
    assert tracing.busy_s(tr) == pytest.approx(30e-9)
    assert 1 - tracing.busy_s(tr) / tr.window_s == pytest.approx(0.7)
    # longest first, each named by the innermost host span around its middle
    assert tracing.idle_gaps(tr) == [["bench/deliver", pytest.approx(40e-9)],
                                     ["bench/step", pytest.approx(20e-9)],
                                     ["bench/step", pytest.approx(10e-9)]]


VISIT = ("%closed_call.33 = (f32[1,1,8,1]{3,2,1,0:T(8,128)S(1)}, f32[1,1,8,1]{3,2,1,0}) "
         "custom-call(s32[8]{0} %fusion.582, f32[1048577,128]{1,0} %get-tuple-element.4963)")
BARE = "%closed_call.7 = (f32[1,4,8,1], f32[1,4,8,1]) custom-call(s32[32] %a, f32[8,128] %b)"
IVF = "%_ivf_score.1 = f32[32,128]{1,0} custom-call(f32[32,128]{1,0} %q, f32[128,128]{1,0} %c)"
FILTER = ("%gather_score.2 = (f32[32,64,8,1]{3,2,1,0}, s32[32,64,8,1]{3,2,1,0}) "
          "custom-call(s32[16384]{0} %ids, f32[1048577,128]{1,0} %rows)")


def test_busy_is_averaged_over_chips_and_kernels_found_by_their_text():
    # an XLA Ops event is named by its HLO text; an operation by its name there
    assert [tracing._short(t) for t in (VISIT, BARE, IVF, FILTER)] == [
        "closed_call.33", "closed_call.7", "_ivf_score.1", "gather_score.2"]
    loop = tracing.Op("while.1", 0, 60)
    tr = tracing.Trace(
        ops={0: [loop, tracing.Op("closed_call.33", 5, 45),
                 tracing.Op("gather_score.2", 45, 50)],
             1: [tracing.Op("closed_call.7", 0, 20),
                 tracing.Op("_ivf_score.1", 20, 30)]},
        spans=[], window=(0, 100))
    assert tracing.busy_s(tr) == pytest.approx(45e-9)
    # own time: the loop's 60 less the 45 of the two kernels inside it
    assert tracing.top_ops(tr) == [["closed_call.33", pytest.approx(40e-9)],
                                   ["closed_call.7", pytest.approx(20e-9)],
                                   ["while.1", pytest.approx(15e-9)],
                                   ["_ivf_score.1", pytest.approx(10e-9)],
                                   ["gather_score.2", pytest.approx(5e-9)]]


def test_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/step"):
        f(x).block_until_ready()
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("bench/deliver"):
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = tracing.find(str(tmp_path))

    # on the CPU the XLA operations run on the host's client threads
    def cpu_ops(plane, line):
        return 0 if plane.startswith("/host:CPU") and "XLAPjRtCpuClient" in line else None

    tr = tracing.read(path, ops_line=cpu_ops)
    assert 0.07 <= tr.window_s < 1.0
    assert {sp[0] for sp in tr.spans} >= {"bench/step", "bench/deliver"}
    busy = tracing.busy_s(tr)
    assert busy is not None and 0 < busy < 0.05
    gaps = tracing.idle_gaps(tr)
    assert gaps[0][0] == "bench/step" and gaps[0][1] > 0.04
    assert sum(g[1] for g in tracing.idle_gaps(tr, n=1000)) == pytest.approx(
        tr.window_s - busy, rel=1e-6)
    # with no TPU plane the device reduction finds no operations
    assert tracing.busy_s(tracing.read(path)) is None
