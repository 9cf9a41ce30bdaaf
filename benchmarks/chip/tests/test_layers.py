"""Device time by program layer and idle time by cause (``layers.py``):
on a synthetic trace with known scopes, gaps and clocks, on the small
TPU traces in ``data/``; and the readings of ``trace.py`` on the
committed trace, pinned where they stand."""
import json
from types import SimpleNamespace

import pytest

from benchmarks.chip import layers, trace
from conftest import DATA

OLD_TRACE = DATA / "tpu_trace.xplane.pb"
SCOPED = DATA / "tpu_scoped_trace"

# a program's optimized HLO, as ``Compiled.as_text()`` prints it
TEXT = """HloModule jit_prog, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%p), metadata={op_name="jit(prog)/llm.eval/tanh"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %sin.4 = f32[8]{0} sine(%x), metadata={op_name="jit(prog)/llm.step/while/body/vmap(transpose(jvp()))/sin"}
  %copy.5 = f32[8]{0} copy(%x)
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%i, %sin.4)
}

ENTRY %main (a: f32[8,8], b: f32[8,8]) -> f32[8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %slice.11 = f32[8,8]{1,0} slice(%a), slice={[0:8], [0:8]}
  %convolution.1 = f32[8,8]{1,0} convolution(%slice.11, %b), metadata={op_name="jit(prog)/llm.step/transpose(jvp(model.head))/dot_general"}
  %copy.2 = f32[8,8]{1,0} copy(%convolution.1)
  %reduce.3 = f32[8]{0} reduce(%copy.2, %c), dimensions={1}, to_apply=%add, metadata={op_name="jit(prog)/llm.step/checkpoint/rematted_computation/reduce_sum"}
  %fusion.7 = f32[8]{0} fusion(%reduce.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(prog)/llm.eval/tanh"}
  %negate.8 = f32[8]{0} negate(%fusion.7), metadata={op_name="jit(prog)/neg"}
  ROOT %w = (s32[], f32[8]{0}) while(%t0), condition=%cond, body=%body
}
"""


def test_scope_map_follows_operands_users_then_the_computation():
    m = layers.scope_map(TEXT)
    assert "add.9" not in m and "tanh.1" not in m   # run inside callers
    assert m["convolution.1"].endswith("transpose(jvp(model.head))/"
                                       "dot_general")
    assert m["copy.2"] == m["convolution.1"]           # from its operand
    assert m["slice.11"] == m["convolution.1"]         # from its user
    assert m["copy.5"] == m["sin.4"]                   # its computation's
    assert m["negate.8"] == m["fusion.7"]              # no scope of its own
    assert layers.innermost(m["reduce.3"]) == "llm.step (recompute)"
    assert layers.innermost(m["sin.4"]) == "llm.step (backward)"
    assert layers.innermost("jit(prog)/neg") == layers.UNSCOPED


@pytest.mark.parametrize("op_name,part", [
    ("jit(f)/llm.eval/model.head/dot_general", "tail"),
    ("jit(f)/llm.fedavg/mul", "tail"),
    ("jit(f)/llm.step/transpose(jvp(model.head))/dot_general", "head"),
    ("jit(f)/llm.step/vmap(transpose(jvp()))/dot_general", "backward"),
    ("jit(f)/llm.step/checkpoint/rematted_computation/mul", "backward"),
    ("jit(f)/llm.step/llm.adamw/sqrt", "adamw"),
    ("jit(f)/llm.step/dot_general", "forward"),
    ("jit(f)/add", layers.UNSCOPED),
])
def test_llm_parts_by_precedence(op_name, part):
    assert layers.llm_part(op_name) == part


def _xspace(planes) -> bytes:
    """A trace file's bytes: ``planes`` maps a plane's name to its lines,
    each a list of (event name, start ns, duration ns, int stats)."""
    from jax.profiler import ProfileData
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        meta, stats, body = {}, {}, []
        for lid, (lname, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur, st in events:
                mid = meta.setdefault(name, len(meta) + 1)
                ss = " ".join(
                    f"stats {{ metadata_id: "
                    f"{stats.setdefault(k, len(stats) + 1)} "
                    f"int64_value: {v} }}" for k, v in st.items())
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{start * 1000} duration_ps: {dur * 1000} "
                           f"{ss} }}")
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        f"timestamp_ns: 0 {' '.join(evs)} }}")
        body += [f"event_metadata {{ key: {i} value {{ id: {i} "
                 f"name: {json.dumps(n)} }} }}" for n, i in meta.items()]
        body += [f"stat_metadata {{ key: {i} value {{ id: {i} "
                 f"name: {json.dumps(n)} }} }}" for n, i in stats.items()]
        out.append(f"planes {{ id: {pid} name: {json.dumps(pname)} "
                   f"{' '.join(body)} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


MS = 1_000_000          # ns


def _synthetic(tmp_path, with_spans=True):
    """One execution of ``jit_prog`` (run 8), 1.0-3.0 ms on the device's
    clock, enqueued at 1.5 ms and called back at 3.7 ms on the host's: the
    device runs 0.5 ms behind.  On the host's clock its ops are the head
    1.5-2.0, a gap, the backward 2.1-3.1 and a copy of the head's output
    3.1-3.5, which takes the head's scope; the window is 1.0-5.0 ms."""
    host = [("bench.window", 1 * MS, 4 * MS, {}),
            ("bench.call", 1.2 * MS, 3.6 * MS, {}),
            (layers.ENQUEUE, 1.5 * MS, 0.05 * MS, {"run_id": 8}),
            (layers.CALLBACKS, 3.7 * MS, 0.05 * MS, {"run_id": 8})]
    if with_spans:
        host += [("llm.stage", 1.3 * MS, 3.2 * MS, {"step_num": 1}),
                 ("llm.stage.dispatch", 1.4 * MS, 0.2 * MS, {}),
                 ("llm.stage.fetch", 3.5 * MS, 0.9 * MS, {"bytes": 64})]
    ops = [(f"%convolution.1 = f32[8,8]{{1,0}} convolution(f32[8,8] %a, "
            f"f32[8,8] %b)", 1 * MS, int(0.5 * MS), {}),
           ("%w = (s32[]) while((s32[]) %t0), condition=%cond, body=%body",
            int(1.6 * MS), int(1.4 * MS), {}),
           ("%sin.4 = f32[8]{0} sine(f32[8]{0} %x)", int(1.6 * MS),
            1 * MS, {}),
           ("%copy.2 = f32[8,8]{1,0} copy(f32[8,8]{1,0} %convolution.1)",
            int(2.6 * MS), int(0.4 * MS), {})]
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(_xspace({
        "/device:TPU:0": {
            layers.MODULES_LINE: [("jit_prog(77)", 1 * MS, 2 * MS,
                                   {"run_id": 8})],
            trace.OPS_LINE: [(n, int(s), d, st) for n, s, d, st in ops]},
        "/host:CPU": {"python3": [(n, int(s), int(d), st)
                                  for n, s, d, st in host]}}))
    return str(path)


def test_synthetic_trace_layers_clock_and_gaps(tmp_path):
    lay = layers.reduce_trace(_synthetic(tmp_path),
                              lambda mods, n, want: {"jit_prog": TEXT})
    assert lay.delta_s[0] == pytest.approx(0.5e-3)
    assert lay.delta_upper_s[0] == pytest.approx(0.7e-3)
    assert lay.scope_s == {"model.head (backward)": pytest.approx(0.9e-3),
                           "llm.step (backward)": pytest.approx(1.0e-3)}
    assert lay.idle_by_cause == {
        "bench.call": pytest.approx(0.5e-3),        # 1.0-1.5, before stage
        "in program: llm.step (backward)": pytest.approx(0.1e-3),
        "llm.stage.fetch": pytest.approx(1.5e-3)}   # 3.5-5.0
    assert lay.idle_in_program_s == pytest.approx(0.1e-3)
    assert lay.device_s + sum(lay.idle_by_cause.values()) == \
        pytest.approx(lay.window_s)
    assert lay.n_ops == 3 and lay.spans["llm.stage"] == 1

    ctx = SimpleNamespace(summary=object(), layers=lay,
                          window=SimpleNamespace(traced_calls=2))
    assert layers.per_call_ms(
        ctx, lambda n: layers.llm_part(n) == "head") == pytest.approx(0.45)
    assert layers.per_call_ms(
        ctx, lambda n: layers.llm_part(n) == "backward") == \
        pytest.approx(0.5)


def test_a_program_without_spans_reads_as_nothing(tmp_path):
    path = _synthetic(tmp_path, with_spans=False)
    assert layers.reduce_trace(path, lambda *a: {"jit_prog": TEXT}) is None
    assert layers.reduce_trace(str(OLD_TRACE), lambda *a: {}) is None
    # spans, but a program compiled without scopes (or not found)
    path = _synthetic(tmp_path)
    assert layers.reduce_trace(path, lambda *a: {}) is None
    assert layers.reduce_trace(path, lambda *a: {
        "jit_prog": TEXT.replace("llm.", "x.").replace("model.", "x.")
    }) is None


def test_clock_of_the_committed_trace():
    """Each execution starts 1.40-1.47 ms before its enqueue on the
    committed trace, and ends 1.99-2.11 ms before its callbacks."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(OLD_TRACE))
    spans, enq, cbs = layers._host(pd)
    assert sorted(enq) == sorted(cbs) == [(8, 0), (9, 0), (10, 0)]
    w0, w1 = next((s, e) for n, s, e in spans if n == trace.WINDOW_SPAN)
    d = layers._device(pd.find_plane_with_name("/device:TPU:0"), 0, enq,
                       cbs, w0, w1, n_top=10)
    assert d.delta == pytest.approx(1.468525e-3, abs=1e-9)
    assert d.delta_upper == pytest.approx(1.985599e-3, abs=1e-9)
    assert 1.46e-3 < d.delta < d.delta_upper < 2.06e-3


def test_existing_readings_of_the_committed_trace_are_unchanged():
    s = trace.summarize(*trace.read(str(OLD_TRACE)))
    assert s.window == pytest.approx((0.04255901, 0.20043893), abs=1e-12)
    assert s.busy_s == {0: pytest.approx(0.001880359, abs=1e-12)}
    assert s.class_s == {0: {"other": pytest.approx(0.00169753, abs=1e-12),
                             "matmul": pytest.approx(0.000182829,
                                                     abs=1e-12)}}
    assert [n for n, _ in s.top_ops] == [
        "sine_add_fusion kLoop f32[2048,2048]{1,0:T(8,128)S(1)}",
        "convolution_tanh_fusion kOutput f32[2048,2048]{1,0:T(8,128)S(1)}",
        "reduce_sum f32[]{:T(128)}",
        "copy-start (f32[2048,2048]{1,0:T(8,128)S(1)}, "
        "f32[2048,2048]{1,0:T(8,128)}, u32[]{:S(2)})",
        "copy-done f32[2048,2048]{1,0:T(8,128)S(1)}"]
    assert [t for _, t in s.top_ops] == pytest.approx(
        [0.001685649, 0.000182829, 1.1846e-05, 2.6e-08, 9e-09], abs=1e-12)
    assert [n for n, _ in s.idle_gaps] == ["bench.call"] * 10
    assert [t for _, t in s.idle_gaps][:3] == pytest.approx(
        [0.052877585, 0.05187034, 0.051251311], abs=1e-12)
    assert s.spans == {"bench.window": 1, "bench.call": 3}


def test_the_recorded_scoped_trace():
    """``record_scoped_trace.py`` on one v5e chip: three calls, each with a
    20 ms host callback inside the program under ``qfl.local`` and a 50 ms
    host pause in ``qfl.rounds.unpack`` after it.  The device counts the
    callback as busy time of the op that waits for it; the pause is idle
    put down to the program's span."""
    text = SCOPED.with_suffix(".hlo.txt").read_text()
    lay = layers.reduce_trace(str(SCOPED.with_suffix(".xplane.pb")),
                              lambda mods, n, want: {"jit_step": text})
    assert lay.delta_s[0] == pytest.approx(1.307061e-3, abs=1e-9)
    assert lay.delta_upper_s[0] == pytest.approx(1.808417e-3, abs=1e-9)
    assert set(lay.scope_s) == {"qfl.local", "tape.replay", "model.head"}
    assert 3 * 0.02 < lay.scope_s["qfl.local"] < 3 * 0.02 + 0.01
    assert lay.scope_s["tape.replay"] == pytest.approx(2.546245e-3,
                                                       abs=1e-9)
    assert 3 * 0.05 < lay.idle_by_cause["qfl.rounds.unpack"] < 0.17
    assert lay.idle_by_cause["qfl.rounds.dispatch"] == pytest.approx(
        2.983988e-3, abs=1e-9)
    assert lay.idle_in_program_s < 1e-5
    assert lay.device_s + sum(lay.idle_by_cause.values()) == \
        pytest.approx(lay.window_s)
    assert [g[0] for g in lay.idle_gaps[:3]] == ["qfl.rounds.unpack"] * 3
    assert lay.spans["qfl.rounds"] == lay.spans["qfl.rounds.unpack"] == 3
