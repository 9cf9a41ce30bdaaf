"""The trace reduction, on events written as a TPU trace names them
(the HLO instruction's text)."""

import pytest

from benchmarks.chip import trace
from benchmarks.chip.trace import Op


def test_busy_is_the_union_of_intervals_and_gaps_are_named():
    spans = [("bench.window", 0.0, 10.0), ("bench.call", 0.0, 6.0),
             ("bench.call", 6.5, 10.0)]
    conv = "%convolution.1 = f32[8,8]{1,0} convolution(f32[8,8] %a, f32[8,8] %b)"
    ops = [Op(0, conv, 1.0, 2.0),
           Op(0, "%fusion.7 = f32[8]{0} fusion(f32[8] %x), kind=kLoop",
              2.5, 1.0),                                     # overlaps
           Op(0, "%all-reduce.3 = f32[8]{0} all-reduce(f32[8] %y)", 4.0,
              1.0),
           Op(0, "%fusion.8 = f32[8]{0} fusion(f32[8] %x), kind=kLoop",
              7.0, 1.0),
           Op(1, conv, 0.0, 10.0)]
    s = trace.summarize(ops, spans)
    assert s.busy_s == {0: pytest.approx(4.5), 1: pytest.approx(10.0)}
    assert s.mean_busy_s == pytest.approx(7.25)
    assert s.window_s == 10.0
    assert s.class_s[0]["matmul"] == pytest.approx(2.0)
    assert s.class_s[0]["collective"] == pytest.approx(1.0)
    assert s.class_total("matmul") == pytest.approx(12.0)
    assert s.class_max("collective") == pytest.approx(1.0)
    # device 0 idles 0-1 (call), 3.5-4 (call), 5-7 (call, then between
    # calls at 6.25), 8-10 (call)
    assert s.idle_gaps[0] == ("bench.call", pytest.approx(2.0))
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(5.5)
    assert s.top_ops[0][0] == "convolution f32[8,8]{1,0}"
    assert s.spans == {"bench.window": 1, "bench.call": 2}


def test_ops_outside_the_window_are_clipped():
    spans = [("bench.window", 1.0, 2.0)]
    s = trace.summarize([Op(0, "%fusion.1 = f32[] fusion()", 0.0, 1.5)],
                        spans)
    assert s.busy_s[0] == pytest.approx(0.5)


@pytest.mark.parametrize("text,cls", [
    ("%convolution.12 = f32[8,8]{1,0} convolution(f32[8,8] %a, f32[8,8] %b)",
     "matmul"),
    ("%fusion.874 = (f32[5,16,64,11008]{3,2,1,0}) fusion(f32[5,16,64,22016] "
     "%fusion.871), kind=kOutput, calls=%fused_computation.151", "matmul"),
    ("%convolution_add_fusion.9 = f32[5,16,64,768]{3,2,1,0} fusion(f32[5] "
     "%a), kind=kOutput", "matmul"),
    ("%all-reduce.3 = f32[8,10,4096,8]{} all-reduce(f32[8,10,4096,8] %x)",
     "collective"),
    ("%fusion.9 = f32[19,5,50]{2,1,0} fusion(f32[19,5,50] %all-reduce.3), "
     "kind=kLoop", "other"),                # a collective's consumer
    ("%custom-call.2 = f32[8,8] custom-call(f32[8,8] %a), "
     'custom_call_target="tpu_custom_call", backend_config="matmul_kernel"',
     "other"),
    ("%custom-call.3 = f32[8,8] custom-call(f32[8,8] %a), "
     'custom_call_target="int4_matmul"', "matmul"),
])
def test_op_class(text, cls):
    assert trace.op_class(text) == cls


def test_control_flow_is_read_as_its_body():
    name, opcode, label = trace.parse(
        "%while.244 = (s32[], f32[5,50]) while((s32[]) %tuple.479), "
        "condition=%region_81, body=%region_72")
    assert (name, opcode) == ("while.244", "while")


def test_a_trace_recorded_on_the_chip():
    """``record_trace.py``'s trace of one v5e chip (three calls of a
    matmul, a loop of eight elementwise fusions and a reduction, each
    call followed by a host pause of 50 ms) as the reduction reads it."""
    from pathlib import Path
    path = Path(__file__).resolve().parent / "data" / "tpu_trace.xplane.pb"
    ops, spans = trace.read(str(path))
    s = trace.summarize(ops, spans)
    assert s.spans == {"bench.window": 1, "bench.call": 3}
    assert {op.device for op in ops} == {0}
    kinds = [trace.op_class(op.name) for op in ops]
    assert kinds.count("matmul") == 3                  # one dot a call
    assert "collective" not in kinds
    # the device's clock runs about 1.2 ms ahead of the host's spans here:
    # the first call's ops end before ``bench.window`` opens and are
    # clipped away, and the union is the two later calls' ops
    window = next(sp for sp in spans if sp[0] == "bench.window")
    assert max(op.start + op.dur for op in ops[:12]) < window[1]
    assert s.busy_s[0] == pytest.approx(sum(op.dur for op in ops[12:]),
                                        rel=1e-6)
    assert 0 < s.busy_s[0] < 0.1 * s.window_s
    pauses = s.idle_gaps[:3]
    assert all(name == "bench.call" and 0.05 <= gap < 0.06
               for name, gap in pauses)
    assert s.top_ops[0][0].startswith("sine_add_fusion kLoop")
