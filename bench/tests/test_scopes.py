"""The attribution of device time to engine stages: the rule on a hand-made
compiled program, the join with hand-made operations, and a trace recorded
here of a small scoped program."""
import tempfile
import types

import pytest

from bench import scopes, tracing

LOOP = "jit(mutable_search)/vmap(compass/engine/open)/compass/engine/loop"

HLO = f"""HloModule jit_mutable_search, is_scheduled=true

FileNames
1 "/checkout/src/repro/core/engine/btree_iter.py"

FunctionNames
1 "step"

FileLocations
1 {{file_name_id=1 function_name_id=1 line=34 end_line=34 column=4 end_column=9}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

%fused_computation.2 (param_0: f32[128,1,1048576]) -> f32[128,1,1048576] {{
  %param_0 = f32[128,1,1048576]{{2,1,0}} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[128,1,1048576]{{2,1,0}} dynamic-update-slice(%param_0)
}}

%fused_computation.3 (param_0.1: s32[32]) -> s32[32] {{
  %param_0.1 = s32[32]{{0}} parameter(0)
  ROOT %add.9 = s32[32]{{0}} add(%param_0.1, %param_0.1), metadata={{op_name="{LOOP}/while/body/compass/engine/gnext/add"}}
}}

%gather_body (p: (s32[], f32[128,1,1048576])) -> (s32[], f32[128,1,1048576]) {{
  %p = (s32[], f32[128,1,1048576]{{2,1,0}}) parameter(0)
  %dynamic-slice_dynamic-update-slice_fusion.2 = f32[128,1,1048576]{{2,1,0:T(8,128)}} fusion(%p), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple.1 = (s32[], f32[128,1,1048576]{{2,1,0}}) tuple(%p)
}}

%gather_cond (p.1: (s32[], f32[128,1,1048576])) -> pred[] {{
  ROOT %lt.1 = pred[] compare(%p.1), direction=LT
}}

%bnext_body (q: (s32[], f32[128,1,1048576])) -> (s32[], f32[128,1,1048576]) {{
  %q = (s32[], f32[128,1,1048576]{{2,1,0}}) parameter(0)
  ROOT %while.674 = (s32[], f32[128,1,1048576]{{2,1,0}}) while(%q), condition=%gather_cond, body=%gather_body
}}

%bnext_cond (q.1: (s32[], f32[128,1,1048576])) -> pred[] {{
  ROOT %lt.2 = pred[] compare(%q.1), direction=LT
}}

%cmp (a: f32[], b: f32[]) -> pred[] {{
  ROOT %lt.3 = pred[] compare(%a, %b), direction=LT
}}

%loop_body (r: (s32[32], f32[32,64])) -> (s32[32], f32[32,64]) {{
  %r = (s32[32]{{0}}, f32[32,64]{{1,0}}) parameter(0)
  %while.80 = (s32[], f32[128,1,1048576]{{2,1,0}}) while(%r), condition=%bnext_cond, body=%bnext_body, metadata={{op_name="{LOOP}/while/body/compass/engine/bnext/while" stack_frame_id=1}}
  %fusion.3 = s32[32]{{0}} fusion(%r), kind=kLoop, calls=%fused_computation.3
  %copy.5 = s32[32]{{0}} copy(%r)
  %sort.4 = (f32[32,64]{{1,0}}, s32[32,64]{{1,0}}) sort(%r), dimensions={{1}}, to_apply=%cmp, metadata={{op_name="{LOOP}/while/body/compass/engine/gnext/vmap(compass/engine/sort)/jit(argsort)/sort"}}
  %custom-call.1 = (f32[1,1,8,1]{{3,2,1,0}}, f32[1,1,8,1]{{3,2,1,0}}) custom-call(%r), custom_call_target="tpu_custom_call", metadata={{op_name="{LOOP}/while/body/compass/engine/visit/vmap(compass/visit_step)/pallas_call"}}
  ROOT %tuple.2 = (s32[32]{{0}}, f32[32,64]{{1,0}}) tuple(%r)
}}

%loop_cond (s: (s32[32], f32[32,64])) -> pred[] {{
  ROOT %lt.4 = pred[] compare(%s), direction=LT
}}

ENTRY %main.1 (arg: f32[32,64]) -> f32[32,64] {{
  %arg = f32[32,64]{{1,0}} parameter(0)
  %while.637 = (s32[32]{{0}}, f32[32,64]{{1,0}}) while(%arg), condition=%loop_cond, body=%loop_body, metadata={{op_name="{LOOP}/while"}}
  %copy.1 = f32[32,64]{{1,0}} copy(%arg)
  ROOT %copy.2 = f32[32,64]{{1,0}} copy(%copy.1), metadata={{op_name="jit(mutable_search)/compass/mutable/delta/concatenate"}}
}}
"""


def test_innermost_scope_and_the_vmap_form():
    assert scopes.stage_of(f"{LOOP}/while/body/compass/engine/bnext/while") == \
        "compass/engine/bnext"
    assert scopes.stage_of("jit(f)/vmap(compass/engine/gnext)/vmap(compass/visit_step)/x") == \
        "compass/visit_step"
    assert scopes.stage_of("vmap(compass/engine/open)") == "compass/engine/open"
    # a longer name that only starts like a stage is no stage
    assert scopes.stage_of("jit(f)/compass/engine/loopy/add") is None
    assert scopes.stage_of("jit(f)/add") is None and scopes.stage_of(None) is None


def test_the_rule_on_a_compiled_program():
    prog = scopes.Program(HLO)
    placed = {n: prog.placed(n) for n in (
        "dynamic-slice_dynamic-update-slice_fusion.2", "while.674", "fusion.3", "copy.5",
        "sort.4", "custom-call.1", "while.637", "copy.1", "copy.2")}
    assert placed == {
        # no op_name, fused root without one: through the XLA-made gather
        # loop, to the B.NEXT while that holds it
        "dynamic-slice_dynamic-update-slice_fusion.2": ("compass/engine/bnext", "while.80"),
        "while.674": ("compass/engine/bnext", "while.80"),
        "fusion.3": ("compass/engine/gnext", "add.9"),  # the fused root's op_name
        "copy.5": ("compass/engine/loop", "while.637"),  # the loop that holds it
        "sort.4": ("compass/engine/sort", "sort.4"),
        "custom-call.1": ("compass/visit_step", "custom-call.1"),
        "while.637": ("compass/engine/loop", "while.637"),
        "copy.1": (scopes.UNSCOPED, None),  # no scope, no caller
        "copy.2": ("compass/mutable/delta", "copy.2"),
    }
    assert prog.instrs["while.80"].source == "repro/core/engine/btree_iter.py:34"
    assert prog.instrs["dynamic-slice_dynamic-update-slice_fusion.2"].shape == \
        "f32[128,1,1048576]{2,1,0:T(8,128)}"
    assert prog.instrs["sort.4"].opcode == "sort"
    assert prog.main_loop.name == "while.637"
    assert prog.main_loop_body() == {"r", "while.80", "fusion.3", "copy.5", "sort.4",
                                     "custom-call.1", "tuple.2"}
    assert prog.in_main_loop("dynamic-slice_dynamic-update-slice_fusion.2")
    assert not prog.in_main_loop("while.637") and not prog.in_main_loop("copy.1")


def _iteration(t0):
    """One iteration of the engine loop: B.NEXT's gather loop, a sort, the
    kernel, a copy; 100 ns."""
    Op = tracing.Op
    return [Op("while.80", t0, t0 + 40), Op("while.674", t0 + 1, t0 + 39),
            Op("dynamic-slice_dynamic-update-slice_fusion.2", t0 + 2, t0 + 38),
            Op("sort.4", t0 + 40, t0 + 70), Op("custom-call.1", t0 + 70, t0 + 90),
            Op("copy.5", t0 + 90, t0 + 95), Op("fusion.3", t0 + 95, t0 + 100)]


def test_the_join_with_operations():
    Op = tracing.Op
    ops = ([Op("while.637", 10, 220)] + _iteration(15) + _iteration(115)
           + [Op("copy.1", 230, 240), Op("copy.2", 240, 250),
              Op("fusion.3", 400, 450)])  # the last outside any served batch
    spans = [("bench/step", 0, 500), ("compass/serve/pack", 0, 10),
             ("compass/serve_batch/B32xT4", 10, 260), ("compass/serve/unpack", 260, 300)]
    trace = tracing.Trace(ops={0: sorted(ops, key=lambda o: o.start_ns)}, spans=spans,
                          window=(0, 500))
    att = scopes.attribute(trace, {"B32xT4": HLO, "B32xT1": HLO})
    ns = {k: round(v * 1e9, 6) for k, v in att.stage_s.items()}
    assert ns == {
        "compass/engine/loop": 210 - 200 + 2 * 5,  # the loop's own time, and the copies
        "compass/engine/bnext": 2 * (2 + 2 + 36),  # own time of the three nested ops
        "compass/engine/sort": 60,
        "compass/visit_step": 40,
        "compass/engine/gnext": 10,
        "compass/mutable/delta": 10,
        scopes.UNSCOPED: 10 + 50,  # copy.1, and the operation outside any batch
    }
    # stages and unscoped sum to the busy time
    assert sum(att.stage_s.values()) == pytest.approx(att.busy_s, rel=1e-12)
    assert att.batches == ["B32xT4"] and att.loop_iterations == [2]
    assert att.ops[0][:3] == ("B32xT4", "dynamic-slice_dynamic-update-slice_fusion.2",
                              "compass/engine/bnext")
    assert att.ops[0][4] == 2
    assert att.ms_per_batch("compass/engine/sort", 2) == pytest.approx(3e-5)
    assert att.ms_per_batch("compass/engine/select", 2) == 0.0
    # idle: 0-10 under pack, 220-230 and 250-260 under serve_batch, 260-300 under unpack,
    # 300-400 and 450-500 under no compass/serve span
    assert att.idle_s == pytest.approx({"compass/serve/pack": 10e-9,
                                        "compass/serve_batch/B32xT4": 20e-9,
                                        "compass/serve/unpack": 40e-9,
                                        "(none)": 150e-9})
    lines = scopes.report(att, 1)
    assert any(ln.strip().startswith("compass/engine/bnext") for ln in lines)
    assert any("via while.80 (repro/core/engine/btree_iter.py:34)" in ln for ln in lines)


def _run(trace, n_batches=1):
    return types.SimpleNamespace(trace=trace, buckets={"n_batches": n_batches})


def test_readers_read_nothing_without_a_device_trace():
    from bench import spec

    empty = tracing.Trace(ops={}, spans=[], window=(0, 1))
    for name in ("bnext_ms", "gnext_ms", "sort_ms", "visit_ms", "select_ms", "visit_step_ms",
                 "unscoped_share", "loop_steps_per_batch"):
        assert spec.load_reader(name)(_run(empty)) is None, name


def test_a_recorded_trace_of_a_scoped_loop(monkeypatch):
    """A vmapped loop of 7 iterations with scoped stages, traced on the CPU
    under a serve_batch span: the readers see what the program did."""
    import jax
    import jax.numpy as jnp

    from bench import spec
    from repro.obs import profiling

    def one(x):
        with profiling.stage_scope("engine/open"):
            x = jnp.sort(x)
        with profiling.stage_scope("engine/loop"):
            def body(c):
                i, x = c
                with profiling.stage_scope("engine/bnext"):
                    x = jnp.sort(x * 1.5 - i)
                return i + 1, x

            return jax.lax.while_loop(lambda c: c[0] < 7, body, (0, x))[1]

    f = jax.jit(jax.vmap(one))
    x = jnp.ones((4, 4096))
    served = profiling.ServedPrograms()
    served.record("B4xT1", lambda: f.lower(x).compile().as_text())
    monkeypatch.setattr(profiling, "SERVED", served)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        tracing.start(d)
        with jax.profiler.TraceAnnotation("bench/step"):
            with jax.profiler.TraceAnnotation("compass/serve_batch/B4xT1"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()

        def cpu_ops(plane, line):
            return 0 if plane.startswith("/host:CPU") and "XLAPjRtCpuClient" in line else None

        trace = tracing.read(tracing.find(d), ops_line=cpu_ops)
    run = _run(trace)
    assert spec.load_reader("loop_steps_per_batch")(run) == 7
    bnext = spec.load_reader("bnext_ms")(run)
    assert bnext > 0 and spec.load_reader("select_ms")(run) == 0.0
    att = scopes.stages(run)
    assert att.stage_s["compass/engine/open"] > 0
    assert sum(att.stage_s.values()) == pytest.approx(att.busy_s, rel=1e-9)
    # the loop's sorts ran 7 times, each inside the loop and placed in B.NEXT
    sorts = [r for r in att.ops if r[2] == "compass/engine/bnext" and r[4] == 7]
    assert sorts and att.programs["B4xT1"].in_main_loop(sorts[0][1])
