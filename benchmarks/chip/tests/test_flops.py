"""The yardstick's arithmetic against hand counts."""
import json

import pytest

from benchmarks.chip import flops, llm, ref_rounds

from conftest import REPO

CONFIGS = REPO / "benchmarks" / "chip" / "configs"


def dims(name):
    return llm.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,hand", [
    # embed 102400x4096 + head 4096x102400 + 8 x (4096x4096 q + 4096x8192
    # kv + 4096x4096 o + 4096x22016 in + 11008x4096 out + 2 norms) + norm
    ("deepseek-llm-7b-base",
     2 * 102400 * 4096 + 8 * (16_777_216 + 33_554_432 + 16_777_216
                              + 90_177_536 + 45_088_768 + 2 * 4096) + 4096),
    # tied embed 50257x768 + 12 x (768x768 + 768x1536 + 768x768 + 768x6144
    # + 3072x768 + 2 norms) + norm
    ("gpt2", 50257 * 768 + 12 * (589_824 + 1_179_648 + 589_824 + 4_718_592
                                 + 2_359_296 + 2 * 768) + 768),
])
def test_params_match_hand_counts(name, hand):
    assert flops.params(dims(name)) == hand


def test_hand_counts_round_to_the_published_figures():
    d = dims("deepseek-llm-7b-base")
    assert round(flops.params(d) / 1e9, 3) == 2.458
    # all 30 layers: the published model's 6.9B
    assert round(flops.params(dict(d, n_layers=30)) / 1e9, 2) == 6.91
    assert round(flops.params(dims("gpt2")) / 1e6, 1) == 151.9


def test_model_flops_per_trained_token():
    d = dims("deepseek-llm-7b-base")
    one = flops.model_flops(d, train_tokens=1, eval_tokens=0, eval_rows=0,
                            seq=64)
    n = flops.layer_matmul_params(d) + flops.head_params(d)
    attn = 3 * 4 * 8 * 64 * 32 * 128
    assert one == 4 * n + 6 * flops.lora_params(d) + attn


def test_executed_matmuls_add_the_recomputed_forward():
    """Remat recomputes the forward: the base projections run three
    passes per trained token (forward, recompute, input gradient) where
    the model count has two."""
    d = dims("gpt2")
    rows = flops.executed_matmuls(d, clients=1, batch=1, seq=64, steps=1,
                                  eval_rows=0)
    proj = sum(f for n, f, _ in rows if n.split(".")[0] in
               ("wq", "wkv", "wo", "w_in", "w_out") and n.endswith(".train")
               and "lora" not in n)
    assert proj == 3 * 2 * 64 * flops.layer_matmul_params(d)


def test_least_time_takes_the_larger_bound():
    rows = [("a", 2e12, 1.0), ("b", 1.0, 8e9)]
    assert flops.least_time_s(rows, 1e12, 1e9) == pytest.approx(2.0 + 8.0)


def test_circuit_flops_of_the_vqc():
    one, two = ref_rounds.gate_counts(4, 2, 3)
    # ZZFeatureMap x2: 4 H + 4 P + 6 pair phases (1q) and 12 CX (2q);
    # RealAmplitudes: 16 RY (1q) and 3 x 6 CX (2q)
    assert (one, two) == (2 * 14 + 16, 2 * 12 + 18)
    assert flops.circuit_flops_per_eval(4, one, two) == 16 * (14 * 44
                                                             + 16 * 42)
