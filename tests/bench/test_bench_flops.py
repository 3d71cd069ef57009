"""The benchmark's operation and byte counts against hand counts, and its
table of peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import tiny  # noqa: E402

import flops  # noqa: E402


def test_lowrank_fwd_counts_match_hand_count():
    # x (32, 896) @ R^T (896, 256) @ L^T (256, 4864), bf16
    c = flops.lowrank_fwd(32, 896, 256, 4864)
    assert c["flops"] == 2 * 32 * 896 * 256 + 2 * 32 * 256 * 4864
    assert c["bytes"] == 2 * (32 * 896 + 256 * 896 + 4864 * 256 + 32 * 4864)


def test_least_time_takes_the_binding_bound():
    peak = flops.peaks("TPU v5 lite")
    # M = 32 is far below the ridge: bytes bind
    c = flops.lowrank_fwd(32, 896, 256, 4864)
    assert flops.least_time_s(c["flops"], c["bytes"], peak) == \
        pytest.approx(c["bytes"] / 819e9)
    # M = 4096: operations bind
    c = flops.lowrank_fwd(4096, 896, 256, 4864)
    assert flops.least_time_s(c["flops"], c["bytes"], peak) == \
        pytest.approx(c["flops"] / 197e12)


def test_model_flops_match_hand_count_at_smoke_size():
    cfg = tiny("qwen2-train-wasi")["cfg"]
    # per layer: factored linears 2K(I+O), attention 2*2*H*Dh*ctx
    lin = (2 * 16 * (64 + 64) + 2 * 8 * (64 + 32) * 2 + 2 * 16 * (64 + 64)
           + 2 * 16 * (64 + 128) * 2 + 2 * 16 * (128 + 64))
    ctx = 10
    attn = 2 * 2 * 4 * 16 * ctx
    head = 2 * 64 * 256
    assert flops.lm_token_flops(cfg, ctx) == 2 * (lin + attn) + head
    seq = 64
    assert flops.lm_sequence_flops(cfg, seq) == pytest.approx(
        seq * (2 * (lin + 2 * 2 * 4 * 16 * (seq + 1) / 2) + head))
    assert flops.train_step_flops(cfg, 2, seq) == pytest.approx(
        3 * 2 * flops.lm_sequence_flops(cfg, seq))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        flops.peaks("TPU v9 imaginary")
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def _lm_token_flops_before(cfg: dict, ctx: float) -> float:
    """``flops.lm_token_flops`` as it counted before layer parts: every
    plan site in every layer, causal attention, the head."""
    m = cfg["model"]
    per_layer = sum(flops.linear_flops(s) for s in cfg["plan"]["sites"].values())
    attn = 2 * 2 * m["num_attention_heads"] * m["head_dim"] * ctx
    head = 2 * m["hidden_size"] * cfg["plan"]["padded_vocab"]
    return m["num_hidden_layers"] * (per_layer + attn) + head


@pytest.mark.parametrize("ctx", [1.0, 1024.5, 2048.0, 777.25])
def test_qwen2_count_is_bit_for_bit_as_before(ctx):
    import harness

    w = harness.cell(harness.load_spec(), "qwen2-train-wasi")
    cfg, mix = w["cfg"], w["mix"]
    assert flops.lm_token_flops(cfg, ctx) == _lm_token_flops_before(cfg, ctx)
    seq = mix["seq"]
    before = 3 * mix["batch"] * seq * _lm_token_flops_before(cfg, (seq + 1) / 2)
    assert flops.train_step_flops(cfg, mix["batch"], seq) == before
