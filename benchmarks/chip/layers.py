"""Device time by program layer, and idle time put down to host work, on
one clock.

``trace.py`` reduces a traced run to busy time, operation classes and
gaps named by the benchmark's own spans.  This module reads the same
trace for what the program writes into it, and for the clock:

- **Layers.**  The program names its layers with ``jax.named_scope``
  (``llm.step``, ``model.head``, ``qfl.local``, ``tape.replay``, ...).  A
  scope lands in the ``op_name`` metadata of the optimized HLO, not in
  the trace, whose operations carry only the instruction's text.  So the
  optimized HLO of each program the trace executed is read from the
  run's compilation cache (the entry of that module name whose
  instructions cover most of the trace's), and each device operation
  inside an ``XLA Modules`` execution is looked up there by instruction
  name.  An instruction with no scope of its own takes its first
  operand's, else its first user's, else the op_name path all scoped
  instructions of its computation share; else it is ``unscoped``.
- **One clock.**  Each ``XLA Modules`` execution is paired with the
  host's ``DoEnqueueProgram`` and ``CompleteCallbacks`` of the same
  ``run_id``.  Device time is shifted by the least delta for which no
  execution starts before its enqueue; the least gap from an
  execution's end to its callbacks bounds the delta from above.
- **Idle gaps** (per device, between the shifted operations, inside the
  ``bench.window`` span) are labelled ``in program: <scope of the next
  operation>`` where they lie inside an execution, else by the innermost
  program span (``llm.*``, ``qfl.*``), else benchmark span, that covers
  their midpoint.

The readings of ``trace.py`` are not touched: they stay on the device's
own clock.  A run whose trace holds no program span or no device
operation, or whose compiled programs name no scope (a program that
writes neither), reads as nothing.

    python -m benchmarks.chip.layers <trace.xplane.pb> <cache dir>
"""
from __future__ import annotations

import bisect
import heapq
import json
import re
import sys
import traceback
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from benchmarks.chip.trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, \
    _CONTAINER, parse

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
CALLBACKS = "CompleteCallbacks"
PROGRAM_SPAN = re.compile(r"^(llm|qfl)\.")
SCOPE = re.compile(r"\b(?:llm|qfl|nm|tape|model)\.[a-z_]+")
UNSCOPED = "unscoped"
IN_PROGRAM = "in program: "
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_NAME = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
# instructions whose computations run inside another instruction
_INNER = re.compile(r"(?:to_apply|calls)=%([\w.\-]+)")


@dataclass
class Layers:
    """What one traced run's program layers read, on the host's clock."""
    delta_s: Dict[int, float]           # device clock shift per device
    delta_upper_s: Dict[int, Optional[float]]
    window_s: float
    n_devices: int
    device_s: float                     # op time in the window, all devices
    scope_s: Dict[str, float]           # innermost scope -> op time
    opname_s: Dict[str, float]          # effective op_name -> op time
    idle_by_cause: Dict[str, float]
    idle_in_program_s: float            # all devices
    n_ops: int                          # device ops read in the window
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    spans: Dict[str, int] = field(default_factory=dict)

    def time_where(self, pred) -> float:
        """Op time (all devices) of the op_names ``pred`` accepts."""
        return sum(t for name, t in self.opname_s.items() if pred(name))

    def summary(self) -> dict:
        return {"delta_ms": {d: v * 1e3 for d, v in self.delta_s.items()},
                "delta_upper_ms": {d: (None if v is None else v * 1e3)
                                   for d, v in self.delta_upper_s.items()},
                "device_scopes": dict(sorted(self.scope_s.items(),
                                             key=lambda t: -t[1])),
                "idle_by_cause": dict(sorted(self.idle_by_cause.items(),
                                             key=lambda t: -t[1])),
                "idle_gaps": self.idle_gaps, "spans": self.spans,
                "n_ops": self.n_ops}


# ---------------------------------------------------------------------------
# the compiled text: instruction name -> op_name
# ---------------------------------------------------------------------------
def scopes_of(op_name: str) -> List[str]:
    return SCOPE.findall(op_name or "")


def scope_map(text: str) -> Dict[str, str]:
    """Instruction name -> the op_name its time is put down to (``""``
    where no program scope reaches it).  Instructions of computations run
    inside another instruction (fused computations, reducers) are left
    out: the trace names only their caller."""
    comp, rows, inner = None, [], set()
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                m = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line)
                comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        inner.update(_INNER.findall(rest))
        op = _OP_NAME.search(rest)
        head = rest.split(", metadata=", 1)[0]
        rows.append((comp, m.group(1), op.group(1) if op else "",
                     _OPERAND.findall(head)))
    own: Dict[str, str] = {}
    by_comp: Dict[str, List[List[str]]] = defaultdict(list)
    for comp, name, op, _ in rows:
        if comp not in inner and scopes_of(op):
            own[name] = op
            by_comp[comp].append(op.split("/"))
    shared = {c: "/".join(_common(ps)) for c, ps in by_comp.items()}
    rows = [r for r in rows if r[0] not in inner]
    users: Dict[str, List[str]] = defaultdict(list)
    out: Dict[str, str] = {}
    for comp, name, op, operands in rows:          # defs before uses
        for o in operands:
            users[o].append(name)
        out[name] = own.get(name) or (
            out.get(operands[0], "") if operands else "")
    for comp, name, op, operands in reversed(rows):
        if not out[name]:
            out[name] = next((out[u] for u in users[name] if out[u]), "")
        if not out[name] and scopes_of(shared.get(comp, "")):
            out[name] = shared[comp]
    return out


def _common(paths: List[List[str]]) -> List[str]:
    """The longest leading run of components all paths share."""
    pre = paths[0]
    for p in paths[1:]:
        n = 0
        while n < min(len(pre), len(p)) and pre[n] == p[n]:
            n += 1
        pre = pre[:n]
    return pre


def innermost(op_name: str) -> str:
    s = scopes_of(op_name)
    if not s:
        return UNSCOPED
    kind = ("recompute" if "rematted_computation" in op_name
            else "backward" if "transpose(" in op_name else "")
    return f"{s[-1]} ({kind})" if kind else s[-1]


def cached_texts(cache_dir: Path, modules, n_devices: int = 1,
                 want: Optional[Dict[str, set]] = None) -> Dict[str, str]:
    """The optimized HLO text of each module name in ``modules``, read
    from JAX's compilation cache in ``cache_dir``.  Where several entries
    share a name, the one whose instructions cover most of ``want[name]``
    (the instruction names the trace executed) is taken."""
    import jax
    from jax._src import compilation_cache as cc
    from jax._src.lib import xla_client as xc

    backend = jax.devices()[0].client
    devices = xc.DeviceList(tuple(jax.devices()[:n_devices]))
    out = {}
    for mod in modules:
        best, best_hits = None, -1
        for path in sorted(Path(cache_dir).glob(f"{mod}-*-cache")):
            if not re.fullmatch(re.escape(mod) + r"-[0-9a-f]+-cache",
                                path.name):
                continue
            raw = cc.decompress_executable(path.read_bytes())
            ser, _ = cc.extract_executable_and_time(raw)
            exe = backend.deserialize_executable(ser, devices, None)
            text = exe.hlo_modules()[0].to_string()
            hits = len(want.get(mod, set()) & set(_NAME.findall(text))
                       ) if want else 0
            if hits > best_hits:
                best, best_hits = text, hits
        if best is not None:
            out[mod] = best
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
@dataclass
class _Device:
    execs: List[Tuple[float, float, str, int]]       # start, end, module, run
    # (module, instruction) -> op time in the window (shifted)
    op_s: Dict[Tuple[Optional[str], str], float]
    # (module, instruction) of the op after a gap inside an execution ->
    # the gaps' time
    in_program: Dict[Tuple[Optional[str], str], float]
    # gaps not inside the execution of the op after them (start, end,
    # that op or None), and the longest of those inside (length, start,
    # that op)
    gaps: List[Tuple[float, float, Optional[Tuple[Optional[str], str]]]]
    top: List[Tuple[float, float, Tuple[Optional[str], str]]]
    delta: float = 0.0
    delta_upper: Optional[float] = None
    n_ops: int = 0


def _host(pd):
    """(spans, enqueue starts, callback starts) of the host planes: the
    benchmark's and the program's spans as (name, start, end); run id ->
    start of its ``DoEnqueueProgram`` / ``CompleteCallbacks``."""
    spans, enq, cbs = [], {}, {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) or not plane.name.startswith(
                "/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith("bench.") or PROGRAM_SPAN.match(name):
                    s = ev.start_ns * 1e-9
                    spans.append((name, s, s + ev.duration_ns * 1e-9))
                elif name == ENQUEUE or name == CALLBACKS:
                    st = dict(ev.stats)
                    if "run_id" in st:
                        key = (int(st["run_id"]),
                               int(st.get("device_ordinal", 0)))
                        table = enq if name == ENQUEUE else cbs
                        table.setdefault(key, ev.start_ns * 1e-9)
    return spans, enq, cbs


def _device(plane, dev: int, enq, cbs, w0: float, w1: float,
            n_top: int) -> _Device:
    """One device plane in one pass over its ops (millions in a traced
    second of the fused rounds): op time by (module, instruction), and the
    gaps between the merged op intervals, on the host's clock."""
    lines = {line.name: line for line in plane.lines}
    execs = []
    if MODULES_LINE in lines:
        for ev in lines[MODULES_LINE].events:
            st = dict(ev.stats)
            s = ev.start_ns * 1e-9
            execs.append((s, s + ev.duration_ns * 1e-9,
                          ev.name.split("(", 1)[0],
                          int(st.get("run_id", -1))))
    execs.sort()
    lo = [enq[(r, dev)] - s for s, e, m, r in execs if (r, dev) in enq]
    hi = [cbs[(r, dev)] - e for s, e, m, r in execs if (r, dev) in cbs]
    delta = max(lo) if lo else 0.0
    d = _Device(execs=[(s + delta, e + delta, m, r) for s, e, m, r in execs],
                op_s={}, in_program={}, gaps=[], top=[], delta=delta,
                delta_upper=min(hi) if hi else None)
    if OPS_LINE not in lines:
        d.gaps.append((w0, w1, None))
        return d

    starts = [x[0] for x in d.execs] + [float("inf")]
    ends = [x[1] for x in d.execs] + [float("inf")]
    mods = [x[2] for x in d.execs] + [None]
    memo: Dict[str, Optional[str]] = {}
    # op and in-program gap time by instruction, per module (None: ops
    # outside every execution); keyed by module only when it changes
    ops_by = defaultdict(lambda: defaultdict(float))
    idle_by = defaultdict(lambda: defaultdict(float))
    gaps, top = d.gaps, d.top
    k = 0                           # the execution an op may lie in
    mod = mods[0]
    ops_in, idle_in, ops_out = ops_by[mod], idle_by[mod], ops_by[None]
    cur_e = w0                      # end of the merged intervals so far
    shortest = 0.0                  # of the longest gaps kept in ``top``
    n_ops = 0
    for ev in lines[OPS_LINE].events:
        text = ev.name
        try:
            instr = memo[text]
        except KeyError:
            name, opcode, _ = parse(text)
            instr = memo[text] = None if _CONTAINER.match(opcode) else name
        if instr is None:
            continue
        s = ev.start_ns * 1e-9 + delta
        e = s + ev.duration_ns * 1e-9
        if e <= w0 or s >= w1:
            continue
        if ends[k] < s:
            while ends[k] < s:
                k += 1
            mod = mods[k]
            ops_in, idle_in = ops_by[mod], idle_by[mod]
        inside = starts[k] <= s
        if s < w0:
            s = w0
        if e > w1:
            e = w1
        if inside:
            ops_in[instr] += e - s
        else:
            ops_out[instr] += e - s
        n_ops += 1
        if s > cur_e:               # a gap, ended by this op
            g = s - cur_e
            if starts[k] <= (cur_e + s) / 2:
                idle_in[instr] += g
                if g > shortest:
                    item = (g, cur_e, (mod, instr))
                    if len(top) < n_top:
                        heapq.heappush(top, item)
                    else:
                        heapq.heapreplace(top, item)
                    if len(top) == n_top:
                        shortest = top[0][0]
            else:
                gaps.append((cur_e, s, (mod if inside else None, instr)))
        if e > cur_e:
            cur_e = e
    d.op_s = {(m, i): t for m, ts in ops_by.items() for i, t in ts.items()}
    d.in_program = {(m, i): t for m, ts in idle_by.items()
                    for i, t in ts.items()}
    if cur_e < w1:
        gaps.append((cur_e, w1, None))
    d.n_ops = n_ops
    return d


def reduce_trace(path: str, texts_for, n_top: int = 10) -> Optional[Layers]:
    """The layers of one trace file.  ``texts_for(modules, n_devices,
    want)`` returns module name -> compiled text.  None where the trace
    holds no program span or no device operation in the window, or where
    no compiled text names a program scope."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, enq, cbs = _host(pd)
    if not any(PROGRAM_SPAN.match(n) for n, _, _ in spans):
        return None
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        return None
    w0, w1 = win[0]
    devs: Dict[int, _Device] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devs[dev] = _device(plane, dev, enq, cbs, w0, w1, n_top)
    if not any(d.op_s for d in devs.values()):
        return None

    want: Dict[str, set] = defaultdict(set)
    for d in devs.values():
        for mod, instr in d.op_s:
            if mod is not None:
                want[mod].add(instr)
    texts = texts_for(sorted(want), len(devs), want)
    maps = {mod: scope_map(t) for mod, t in texts.items()}
    if not any(op for m in maps.values() for op in m.values()):
        return None                 # no compiled text names a scope

    def op_name(key) -> str:
        if key is None:
            return ""
        mod, instr = key
        return maps.get(mod, {}).get(instr, "")

    scope_s: Dict[str, float] = defaultdict(float)
    opname_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    gaps_out = []
    in_prog = 0.0
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda t: (not PROGRAM_SPAN.match(t[0]), t[2] - t[1]))
    for d in devs.values():
        for key, t in d.op_s.items():
            name = op_name(key)
            opname_s[name] += t
            scope_s[innermost(name)] += t
        for key, t in d.in_program.items():
            idle[IN_PROGRAM + innermost(op_name(key))] += t
            in_prog += t
        gaps_out += [(IN_PROGRAM + innermost(op_name(key)), g)
                     for g, _, key in d.top]
        starts = [x[0] for x in d.execs]
        for a, b, nxt in d.gaps:
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and d.execs[i][1] >= mid:
                label = IN_PROGRAM + innermost(op_name(nxt))
                in_prog += b - a
            else:
                label = next((n for n, s, e in inner if s <= mid <= e),
                             WINDOW_SPAN)
            idle[label] += b - a
            gaps_out.append((label, b - a))
    gaps_out.sort(key=lambda t: -t[1])
    counts: Dict[str, int] = defaultdict(int)
    for n, s, e in spans:
        if w0 <= s <= w1:
            counts[n] += 1
    return Layers(delta_s={k: d.delta for k, d in devs.items()},
                  delta_upper_s={k: d.delta_upper for k, d in devs.items()},
                  window_s=w1 - w0, n_devices=len(devs),
                  device_s=sum(opname_s.values()), scope_s=dict(scope_s),
                  opname_s=dict(opname_s), idle_by_cause=dict(idle),
                  idle_in_program_s=in_prog,
                  n_ops=sum(d.n_ops for d in devs.values()),
                  idle_gaps=gaps_out[:n_top],
                  spans=dict(counts))


# ---------------------------------------------------------------------------
# the run's layers, read once for all the metrics that need them
# ---------------------------------------------------------------------------
def for_run(ctx) -> Optional[Layers]:
    """The layers of a ``--trace 1`` run, or None; read once and kept on
    the run's context (``ctx.layers``).  The trace lies where ``run.py``
    records it (``.bench_trace/<cell>`` in the checkout), the compiled
    programs in the compilation cache the run used.  Never raises: a
    failure is logged and reads as nothing."""
    if ctx.summary is None:
        return None
    if not hasattr(ctx, "layers"):
        ctx.layers = None
        try:
            ctx.layers = _read_run(ctx)
        except Exception:  # noqa: BLE001 — a metric reads as nothing
            print(f"layers: not read: {traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    return ctx.layers


def _read_run(ctx) -> Optional[Layers]:
    import time

    import jax

    from benchmarks.chip import trace
    t0 = time.perf_counter()
    root = ctx.cell.dir.parents[1]
    path = trace.find(str(root / ".bench_trace" / ctx.cell.name))
    cache = Path(jax.config.jax_compilation_cache_dir
                 or root / ".jax_cache" / "bench")
    lay = reduce_trace(path, lambda mods, n, want: cached_texts(
        cache, mods, n, want))
    if lay is not None:
        with open(Path(path).parent / "layers.json", "w") as f:
            json.dump(lay.summary(), f, indent=1)
        print(f"layers: read in {time.perf_counter() - t0:.3f} s; "
              f"{json.dumps(lay.summary())}", file=sys.stderr, flush=True)
    return lay


def per_call_ms(ctx, pred) -> Optional[float]:
    """Op time whose effective op_name ``pred`` accepts, per traced call
    and per device, in ms; None where the run has no layers."""
    lay = for_run(ctx)
    if lay is None or ctx.window.traced_calls <= 0:
        return None
    return 1e3 * lay.time_where(pred) / lay.n_devices \
        / ctx.window.traced_calls


def llm_part(op_name: str) -> str:
    """The part of the LLM stage an op belongs to, by precedence: the
    federated tail (``llm.fedavg``, ``llm.eval``), the LM head (forward
    and backward), the decoder's backward or remat recompute, AdamW,
    else the forward."""
    s = set(scopes_of(op_name))
    if "llm.eval" in s or "llm.fedavg" in s:
        return "tail"
    if "model.head" in s:
        return "head"
    if "transpose(" in op_name or "rematted_computation" in op_name:
        return "backward"
    if "llm.adamw" in s:
        return "adamw"
    return "forward" if s else UNSCOPED


if __name__ == "__main__":
    lay = reduce_trace(sys.argv[1], lambda mods, n, want: cached_texts(
        Path(sys.argv[2]), mods, n, want))
    print(json.dumps(lay.summary() if lay else None, indent=1))
