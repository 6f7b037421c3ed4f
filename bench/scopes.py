"""Which engine stage spends each device second of a traced window.

The program names its stages with ``jax.named_scope("compass/<stage>")``
(``repro.obs.profiling.stage_scope``); the names reach the ``op_name``
metadata of the HLO instructions traced inside them, and so the compiled
program's text.  A device operation of the trace is joined to its
instruction by name, within the program that ran it: the one served under
the ``compass/serve_batch/<label>`` host span that encloses the operation
(the profiler puts host spans and device operations on one clock).

The rule, for one instruction:

1. the innermost ``compass/...`` stage in its own ``op_name``
   (``vmap(compass/x)`` included);
2. if it is a fusion, the stage in the ``op_name`` of its fused
   computation's root;
3. otherwise the stage of the instruction that calls the computation that
   holds it (the ``while``, ``conditional``, ``call`` or ``fusion``), by
   the same rule, recursively.

An operation that none of these places, or that no served program names,
is ``unscoped``.  A stage's time is the own time of its operations, nested
operations subtracted (``tracing.self_ns``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
import time
import traceback

from bench import tracing

#: every stage scope the program opens, kernels included
STAGES = (
    "compass/engine/open",
    "compass/planner",
    "compass/engine/loop",
    "compass/engine/bnext",
    "compass/engine/gnext",
    "compass/engine/visit",
    "compass/engine/sort",
    "compass/engine/select",
    "compass/mutable/delta",
    "compass/quant/rerank",
    "compass/visit_step",
    "compass/ivf_score",
    "compass/filter_distance",
    "compass/pq_score",
    "compass/flash_attention",
)
UNSCOPED = "unscoped"
SERVE_BATCH = "compass/serve_batch/"
SERVE_SPANS = "compass/serve"  # serve_batch and the phases around it
LOOP = "compass/engine/loop"

_STAGE = re.compile("(" + "|".join(map(re.escape, STAGES)) + r")(?=[/)]|$)")
_MAIN_WHILE = re.compile(re.escape(LOOP) + r"\)?/while$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_CALL = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
                   r"=%?([\w.\-]+)")
_CALLS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SOURCE = re.compile(r'source_file="([^"]*)"\s+source_line=(\d+)')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
#: the source tables of a compiled module's text, which ``stack_frame_id``
#: metadata points into
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def stage_of(op_name: str | None) -> str | None:
    """The innermost stage scope in an ``op_name``, or None."""
    found = _STAGE.findall(op_name or "")
    return found[-1] if found else None


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    shape: str
    computation: str  # the computation that holds it
    op_name: str | None
    source: str | None  # ``file:line`` of the Python that emitted it
    calls: tuple  # the computations it calls
    body: str | None  # a ``while``'s body computation


def _frame_source(tables: dict, frame: str) -> str | None:
    """``file:line`` of a stack frame of the module's source tables, the
    file from ``repro/`` (or ``bench/``) on."""
    loc = re.search(r"file_location_id=(\d+)", tables["StackFrames"].get(frame, ""))
    loc = tables["FileLocations"].get(loc.group(1), "") if loc else ""
    file_id, line = re.search(r"file_name_id=(\d+)", loc), re.search(r"\bline=(\d+)", loc)
    if file_id is None or line is None:
        return None
    path = tables["FileNames"].get(file_id.group(1), "").strip('"')
    for root in ("/repro/", "/bench/"):
        if root in path:
            path = root[1:] + path.split(root, 1)[1]
            break
    return f"{path}:{line.group(1)}"


def _split_shape(rest: str) -> tuple[str, str]:
    """``"(f32[2], s32[]) tuple(...)"`` -> ``("(f32[2], s32[])", " tuple(...)")``."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, " " + tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rest[: i + 1], rest[i + 1:]
    return rest, ""


class Program:
    """One compiled program's instructions, from its HLO text, and the stage
    of each by the rule of this module."""

    def __init__(self, text: str):
        self.instrs: dict[str, Instr] = {}
        self.roots: dict[str, str] = {}  # computation -> its root instruction
        self.callers: dict[str, str] = {}  # computation -> the instruction calling it
        comp = ""
        tables: dict[str, dict[str, str]] = {t: {} for t in _TABLES}
        for line in text.splitlines():
            if not line.strip() or line.startswith(("HloModule", "}")):
                continue
            if line in _TABLES:
                comp = line
                continue
            if comp in _TABLES and line[0].isdigit():  # ``<id> <value>`` rows
                key, _, value = line.partition(" ")
                tables[comp][key] = value
                continue
            if not line[0].isspace():
                m = _HEADER.match(line)
                comp = m.group(1) if m else ""
                continue
            m = _INSTR.match(line)
            if m is None:
                continue
            name, rest = m.groups()
            meta = rest.split(", metadata={", 1)
            shape, tail = _split_shape(meta[0])
            op = _OPCODE.match(tail)
            called = _CALL.findall(tail) + [c.strip().lstrip("%") for group in
                                            _CALLS.findall(tail) for c in group.split(",")
                                            if c.strip()]
            op_name = src = None
            if len(meta) == 2:
                found = _OP_NAME.search(meta[1])
                op_name = found.group(1) if found else None
                found = _SOURCE.search(meta[1])
                src = f"{found.group(1)}:{found.group(2)}" if found else None
                found = _FRAME.search(meta[1])
                src = src or (f"#{found.group(1)}" if found else None)
            body_of = _BODY.search(tail)
            self.instrs[name] = Instr(name, op.group(1) if op else "", shape, comp, op_name,
                                      src, tuple(called), body_of.group(1) if body_of else None)
            if line.lstrip().startswith("ROOT "):
                self.roots[comp] = name
            for c in called:
                self.callers.setdefault(c, name)
        for ins in self.instrs.values():
            if ins.source is not None and ins.source.startswith("#"):
                ins.source = _frame_source(tables, ins.source[1:])
        self._stage: dict[str, tuple] = {}
        loops = [i for i in self.instrs.values()
                 if i.opcode == "while" and _MAIN_WHILE.search(i.op_name or "")]
        #: the engine loop's ``while``: the one ``lax.while_loop`` opened in
        #: the ``compass/engine/loop`` scope (None unless exactly one)
        self.main_loop = loops[0] if len(loops) == 1 else None

    def placed(self, name: str) -> tuple[str, str | None]:
        """``(stage, name of the instruction whose op_name gave it)``; the
        stage is ``unscoped`` (and the name None) where no rule places it."""
        if name in self._stage:
            return self._stage[name]
        self._stage[name] = (UNSCOPED, None)  # a cycle, were there one, ends here
        ins = self.instrs.get(name)
        out = (UNSCOPED, None)
        if ins is not None:
            stage = stage_of(ins.op_name)
            if stage is not None:
                out = (stage, name)
            else:
                root = None
                if ins.opcode == "fusion" and ins.calls:
                    root = self.instrs.get(self.roots.get(ins.calls[0], ""))
                if root is not None and stage_of(root.op_name) is not None:
                    out = (stage_of(root.op_name), root.name)
                elif ins.computation in self.callers:
                    out = self.placed(self.callers[ins.computation])
        self._stage[name] = out
        return out

    def stage(self, name: str) -> str:
        return self.placed(name)[0]

    def main_loop_body(self) -> set:
        """Names of the instructions directly in the engine loop's body
        computation: each runs once per iteration of the batched loop."""
        loop = self.main_loop
        if loop is None or loop.body is None:
            return set()
        return {i.name for i in self.instrs.values() if i.computation == loop.body}

    def in_main_loop(self, name: str) -> bool:
        """Whether the instruction runs inside the engine loop's ``while``."""
        ins = self.instrs.get(name)
        for _ in range(len(self.instrs)):
            caller = self.callers.get(ins.computation) if ins is not None else None
            if caller is None:
                return False
            if self.main_loop is not None and caller == self.main_loop.name:
                return True
            ins = self.instrs.get(caller)
        return False


@dataclasses.dataclass
class Attribution:
    """A traced window's device time by stage, own time, averaged over chips."""

    stage_s: dict  # stage -> seconds, ``unscoped`` included
    busy_s: float  # union of the operations' intervals (``tracing.busy_s``)
    batches: list  # the ``compass/serve_batch`` labels traced, in order
    loop_iterations: list  # per traced batch: iterations of the engine loop
    ops: list  # [(label, name, stage, own s, events)], longest first
    idle_s: dict  # host span name -> idle seconds of the first chip under it
    programs: dict  # label -> Program

    def ms_per_batch(self, stage: str, n_batches: int) -> float:
        return 1000.0 * self.stage_s.get(stage, 0.0) / n_batches


def attribute(trace: tracing.Trace, texts: dict) -> Attribution:
    """Join the trace's device operations to the programs' instructions and
    sum their own time by stage.  ``texts`` maps each serve_batch label
    (``B32xT4``) to its program's compiled HLO text."""
    programs = {label: Program(text) for label, text in texts.items()}
    lo, hi = trace.window
    spans = sorted((sp for sp in trace.spans
                    if sp[0].startswith(SERVE_BATCH) and sp[2] > lo and sp[1] < hi),
                   key=lambda sp: sp[1])
    labels = [sp[0][len(SERVE_BATCH):] for sp in spans]
    bodies = {label: p.main_loop_body() for label, p in programs.items()}
    stage_ns: dict[str, float] = collections.defaultdict(float)
    op_ns: dict[tuple, float] = collections.defaultdict(float)
    op_events: dict[tuple, int] = collections.Counter()
    per_batch = [collections.Counter() for _ in spans]
    for ops in trace.ops.values():
        j = 0
        for o, own in zip(ops, tracing.self_ns(ops)):
            while j < len(spans) and spans[j][2] < o.start_ns:
                j += 1
            inside = j < len(spans) and spans[j][1] <= o.start_ns
            label = labels[j] if inside else None
            prog = programs.get(label)
            stage = prog.stage(o.name) if prog is not None else UNSCOPED
            stage_ns[stage] += own
            op_ns[(label, o.name, stage)] += own
            op_events[(label, o.name, stage)] += 1
            if inside and o.name in bodies.get(label, ()):
                per_batch[j][o.name] += 1
    chips = max(len(trace.ops), 1)
    ops = sorted(((k[0], k[1], k[2], v * 1e-9 / chips, op_events[k]) for k, v in op_ns.items()),
                 key=lambda r: -r[3])
    return Attribution(
        stage_s={k: v * 1e-9 / chips for k, v in stage_ns.items()},
        busy_s=tracing.busy_s(trace) or 0.0,
        batches=labels,
        loop_iterations=[max(c.values(), default=0) for c in per_batch],
        ops=ops,
        idle_s=idle_under_spans(trace),
        programs=programs,
    )


def idle_under_spans(trace: tracing.Trace, prefix: str = SERVE_SPANS) -> dict:
    """Idle seconds of the first chip under each host span whose name starts
    with ``prefix``, and under none of them (``"(none)"``)."""
    if not trace.ops:
        return {}
    chip = min(trace.ops)
    idle = tracing.gaps([(o.start_ns, o.end_ns) for o in trace.ops[chip]], trace.window)
    spans = sorted((sp for sp in trace.spans if sp[0].startswith(prefix)), key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((sp[2] - sp[1] for sp in spans), default=0)
    out: dict[str, float] = collections.defaultdict(float)
    for s, e in idle:
        covered = []
        k = bisect.bisect_left(starts, e) - 1  # spans that start before the gap ends
        while k >= 0 and starts[k] >= s - longest:
            name, a, b = spans[k]
            cut = (max(a, s), min(b, e))
            if cut[1] > cut[0]:
                out[name] += (cut[1] - cut[0]) * 1e-9
                covered.append(cut)
            k -= 1
        out["(none)"] += (e - s - tracing.union_ns(covered)) * 1e-9
    return dict(out)


def report(att: Attribution, n_batches: int) -> list:
    """The stage table and what goes with it, as lines for standard error."""
    busy = att.busy_s or float("nan")
    lines = [f"stage table: {n_batches} traced micro-batches ({', '.join(att.batches)}); "
             "device ms per batch, share of busy time, own time (nested operations "
             "subtracted); the three longest operations of each stage"]
    for stage, sec in sorted(att.stage_s.items(), key=lambda kv: -kv[1]):
        top = [r for r in att.ops if r[2] == stage][:3]
        lines.append(f"  {stage:26s} {1000.0 * sec / n_batches:12.3f} ms {100 * sec / busy:7.3f}%"
                     "  " + "; ".join(f"{r[1]} [{r[0]}] {1000 * r[3] / n_batches:.3f} ms"
                                      for r in top))
    total = sum(att.stage_s.values())
    lines.append(f"  stages and unscoped sum to {total:.6f} s; busy {att.busy_s:.6f} s "
                 f"({100 * (total - att.busy_s) / busy:+.3f}%)")
    lines.append("longest operations: [program] name: stage, via (where the stage came "
                 "from), own ms per batch, events per batch, in the engine loop, shape")
    for label, name, stage, sec, events in att.ops[:16]:
        prog = att.programs.get(label)
        ins = prog.instrs.get(name) if prog is not None else None
        via = prog.placed(name)[1] if prog is not None else None
        src = prog.instrs[via].source if via in (prog.instrs if prog else {}) else None
        lines.append(
            f"  [{label}] {name}: {stage}, via {via} ({src}), "
            f"{1000 * sec / n_batches:.3f} ms, {events / n_batches:.1f} events, "
            f"in loop {prog.in_main_loop(name) if prog else None}, "
            f"{ins.opcode + ' ' + ins.shape if ins else 'not in the program'}")
    lines.append("engine loop iterations per traced batch (device trace): "
                 + " ".join(map(str, att.loop_iterations)))
    lines.append("idle seconds of the device under each compass/serve host span: "
                 + ", ".join(f"{k} {v:.6f}" for k, v in sorted(att.idle_s.items())))
    return lines


#: the last trace attributed and its attribution: every stage reader of a
#: run reads the one join
_LAST: list = [None, None]


def _served_texts():
    """``(texts, persistent-cache events, backend compiles)``: the compiled
    texts of the programs the process served, or None when the program
    records none (``repro.obs.profiling.SERVED``)."""
    import jax

    from repro.obs import profiling

    served = getattr(profiling, "SERVED", None)
    if served is None:
        return None, {}, 0
    seen: collections.Counter = collections.Counter()

    def on_event(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            seen[event.rsplit("/", 1)[1]] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        texts = served.texts()
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)
    compiles = seen.pop("compiles", 0)
    return texts or None, dict(seen), compiles


def _registry_loop_steps():
    """``compass_loop_steps_total`` over ``compass_serve_batches_total``, each
    summed over every micro-batch the registry recorded, or None."""
    from repro.obs import registry

    got = [registry.registry().get(n) for n in ("compass_loop_steps_total",
                                                 "compass_serve_batches_total")]
    if any(m is None for m in got):
        return None
    steps, batches = (sum(s["value"] for s in m.samples()) for m in got)
    return steps, batches


def stages(run) -> Attribution | None:
    """The attribution of ``run``'s trace, or None where there is no device
    trace or no record of the programs served.  The first call for a trace
    collects the programs' texts, joins, and prints the stage table."""
    if run.trace is None or not run.trace.ops:
        return None
    if _LAST[0] is not run.trace:
        try:
            att = _join(run)
        except Exception:  # a fault of the join must not fail the traced run
            _log("stage join failed; the stage metrics read nothing:\n"
                 + traceback.format_exc())
            att = None
        _LAST[:] = [run.trace, att]
    return _LAST[1]


def _join(run) -> Attribution | None:
    t = time.perf_counter()
    texts, cache, compiles = _served_texts()
    t_texts = time.perf_counter() - t
    if texts is None:
        return None
    att = attribute(run.trace, texts)
    for line in report(att, run.buckets["n_batches"] or len(att.batches) or 1):
        _log(line)
    counted = _registry_loop_steps()
    if counted is not None:
        _log(f"compass_loop_steps_total {counted[0]:.0f} over compass_serve_batches_total "
             f"{counted[1]:.0f}: every micro-batch the registry recorded, warm-up and "
             "whole window included")
    _log(f"stage join: {len(texts)} program texts collected in {t_texts:.3f} s "
         f"({compiles} backend compiles; persistent cache {cache}), joined in "
         f"{time.perf_counter() - t - t_texts:.3f} s, after the window")
    return att


def stage_ms(run, stage: str) -> float | None:
    """Device ms per traced micro-batch of ``stage`` (0.0 where no operation
    of the trace is placed there), or None where nothing can be read."""
    att = stages(run)
    if att is None or not run.buckets["n_batches"]:
        return None
    return att.ms_per_batch(stage, run.buckets["n_batches"])


def within_ms(run, stage: str) -> float | None:
    """Device ms per traced micro-batch of ``stage`` with the stages opened
    inside it, such as the kernels it calls: the operations whose placing
    ``op_name`` holds ``stage`` anywhere, not only as the innermost scope.
    None where nothing can be read."""
    att = stages(run)
    if att is None or not run.buckets["n_batches"]:
        return None
    scope = re.compile(re.escape(stage) + r"(?=[/)]|$)")
    total = 0.0
    for label, name, _, sec, _ in att.ops:
        prog = att.programs.get(label)
        via = prog.placed(name)[1] if prog is not None else None
        if via is not None and scope.search(prog.instrs[via].op_name or ""):
            total += sec
    return 1000.0 * total / run.buckets["n_batches"]


def _log(msg: str) -> None:
    import jax

    dev = jax.devices()
    print(f"[{dev[0].platform} {dev[0].device_kind} x{len(dev)}] {msg}", file=sys.stderr,
          flush=True)
