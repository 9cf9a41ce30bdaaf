"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A traced run records the window with ``jax.profiler`` and writes host
spans of its own (``bench.*``, ``jax.profiler.TraceAnnotation``) into
the same trace.  This module reads the ``.xplane.pb`` with JAX alone:

- device planes are ``/device:TPU:<n>``; their operations are the events
  of the ``XLA Ops`` line, each named by its HLO instruction's text
  (control-flow operations, whose events span their bodies', are left
  out);
- host spans are the ``bench.*`` events of the host plane; the traced
  window is the ``bench.window`` span.

It gives the busy time (union of operation intervals) and idle share per
device, operation time by class (``matmul``, ``collective``, ``other``),
the operations that took most time and the longest idle gaps, each gap
named by the innermost benchmark span that covers it.

    python -m benchmarks.chip.trace <trace.xplane.pb>          # the summary
    python -m benchmarks.chip.trace <trace.xplane.pb> --dump   # its layout
"""
from __future__ import annotations

import glob
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all", re.I)
_MATMUL = re.compile(r"convolution|\bdot\b|dot-general|kind=kOutput|"
                     r"matmul", re.I)
# control flow: its event spans the events of its body
_CONTAINER = re.compile(r"^(while|conditional|call)$")


def parse(text: str) -> Tuple[str, str, str]:
    """(name, opcode, label) of an operation as a TPU trace names it: the
    HLO instruction's text, ``%fusion.874 = f32[...] fusion(...),
    kind=kOutput, calls=...``.  The label drops the instance number and
    keeps the fusion kind and the result type, so repeated instances of
    one operation add up."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%")
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    opcode = m.group(1) if m else name
    out_type = rest[:m.start()].strip() if m else ""
    kind = re.search(r"kind=(k\w+)", rest)
    base = re.sub(r"\.\d+$", "", name)
    label = " ".join(x for x in (base, kind.group(1) if kind else "",
                                 out_type[:96]) if x)
    return name, opcode, label


def op_class(text: str) -> str:
    """``collective``, ``matmul`` or ``other``.  TPU traces give no HLO
    category, so the instruction's own name, opcode, fusion kind and
    custom-call target decide: XLA lowers a dot to a
    ``convolution`` and fuses its consumers into an output fusion
    (``kind=kOutput``); a Pallas matmul kernel (``tpu_custom_call``)
    counts as a matmul when its name says so."""
    name, opcode, _ = parse(text)
    kind = re.search(r"kind=(k\w+)", text)
    custom = re.search(r"custom_call_target=\"([^\"]+)\"", text)
    t = " ".join((name, opcode, kind.group(0) if kind else "",
                  custom.group(1) if custom else ""))
    if _COLLECTIVE.search(t):
        return "collective"
    if _MATMUL.search(t):
        return "matmul"
    return "other"


@dataclass
class Op:
    device: int
    name: str           # the HLO instruction's text
    start: float        # seconds on the trace clock
    dur: float


@dataclass
class Summary:
    window: Tuple[float, float]
    n_devices: int
    busy_s: Dict[int, float]
    class_s: Dict[int, Dict[str, float]]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    spans: Dict[str, int] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def class_total(self, cls: str) -> float:
        return sum(c.get(cls, 0.0) for c in self.class_s.values())

    def class_max(self, cls: str) -> float:
        return max((c.get(cls, 0.0) for c in self.class_s.values()),
                   default=0.0)


def find(trace_dir: str) -> str:
    hits = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def read(path: str):
    """(device ops, host spans) of one trace file; spans are (name,
    start, end) of the benchmark's own ``bench.*`` annotations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev = int(m.group(1))
                for ev in line.events:
                    if _CONTAINER.match(parse(ev.name)[1]):
                        continue
                    ops.append(Op(dev, ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9))
            elif not m:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return ops, spans


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(ops: List[Op], spans, n_top: int = 10) -> Summary:
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    w0, w1 = win[0]
    busy, classes = {}, {}
    by_name: Dict[str, float] = defaultdict(float)
    gaps = []
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda t: t[2] - t[1])
    devices = sorted({o.device for o in ops})
    for dev in devices:
        iv = []
        cls: Dict[str, float] = defaultdict(float)
        for o in ops:
            if o.device != dev:
                continue
            s, e = max(o.start, w0), min(o.start + o.dur, w1)
            if e <= s:
                continue
            iv.append((s, e))
            cls[op_class(o.name)] += e - s
            by_name[parse(o.name)[2]] += e - s
        merged = _union(iv)
        busy[dev] = sum(e - s for s, e in merged)
        classes[dev] = dict(cls)
        edges = [w0] + [x for seg in merged for x in seg] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = next((n for n, s, e in inner if s <= mid <= e),
                             "outside any benchmark span")
                gaps.append((label, b - a))
    top = sorted(by_name.items(), key=lambda t: -t[1])[:n_top]
    gaps.sort(key=lambda t: -t[1])
    counts: Dict[str, int] = defaultdict(int)
    for n, s, e in spans:
        if w0 <= s <= w1:
            counts[n] += 1
    return Summary((w0, w1), len(devices), busy, classes, top,
                   gaps[:n_top], dict(counts))


def dump(path: str, per_line: int = 5) -> None:
    """Print the planes, lines and first events of a trace, with stats."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r} lines={[l.name for l in plane.lines]}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:per_line]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
        sys.exit(0)
    ops, spans = read(sys.argv[1])
    s = summarize(ops, spans)
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "class_s": s.class_s, "top_ops": s.top_ops,
                      "idle_gaps": s.idle_gaps, "spans": s.spans}, indent=1))
