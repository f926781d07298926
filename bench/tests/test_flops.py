"""The dense family's work counts, the shared roofline and the peak table."""

import pytest

from bench import spec
from bench.family import dense, least_time
from bench.peaks import peaks_for

TINY = {"n_layers": 2, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 2, "d_ff": 8, "vocab": 10, "tie_embeddings": False,
        "qkv_bias": True}


def config(name):
    return spec.load_json(spec.BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,count", [("smollm-360m", 361_820_160),
                                        ("chatglm3-6b", 6_243_579_904)])
def test_param_count_matches_the_program(name, count):
    from repro.configs import get_config

    cfg = config(name)
    n = spec.family_module(cfg).n_params(cfg["dims"])
    assert n == count == get_config(name).n_params()


def test_tiny_counts_by_hand():
    c = dense.param_counts(TINY)
    # q 4*2*2 + k,v 2*4*1*2 + o 2*2*4 = 16 + 16 + 16; mlp 3*4*8 = 96
    assert c["layer_matmul"] == 48 + 96
    # + biases (2 + 2*1) * 2 = 8, + norms 2*4 = 8
    assert c["layer"] == 48 + 96 + 8 + 8
    assert c["embed"] == c["lm_head"] == 40
    # weights per pass: 2 layers + final norm + head, 2 bytes each
    assert dense.weight_bytes_per_pass(TINY) == 2 * (2 * 160 + 4 + 40)


def test_decode_work_by_hand():
    f, b = dense.decode_work(TINY, [3, 5])
    # matmuls 2 * 2 layers * 144 = 576 per token, logit row 2 * 4 * 10 = 80;
    # attention 4 * 2 layers * 2 heads * 2 dims * (4 + 6) keys = 320
    assert f == 2 * (576 + 80) + 320
    weights = 2 * (2 * 160 + 4 + 40)
    kv_row = 2 * 2 * 1 * 2 * 2          # k and v, 2 layers, 1 kv head, 2 dims, bf16
    assert b == weights + 2 * 4 * 2 + (10 + 2) * kv_row + 2 * 10 * 2


def test_prefill_work_by_hand():
    f, b = dense.prefill_work(TINY, 3)
    # 3 tokens of matmuls, causal keys 1 + 2 + 3 = 6, one logit row
    assert f == 3 * 576 + 4 * 2 * 2 * 2 * 6 + 80
    weights = 2 * (2 * 160 + 4 + 40)
    assert b == weights + 3 * 4 * 2 + 3 * 16 + 10 * 2


def test_least_time_takes_the_larger_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time(1000, 10, p) == 10.0
    assert least_time(10, 1000, p) == 100.0


def test_peaks_known_and_unknown():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
