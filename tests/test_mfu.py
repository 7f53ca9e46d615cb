"""MFU accounting tests: the analytic transformer FLOPs model is
oracle-tested against a real model.init parameter count so the bench's
MFU denominator can never drift from the model code; device-kind ->
generation mapping feeds the peak-FLOPs lookup."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.parallel import mfu, topology


def test_transformer_param_count_matches_model_init():
    from batch_shipyard_tpu.models import transformer as tfm
    config = tfm.TransformerConfig(
        vocab_size=1024, d_model=128, n_layers=2, n_heads=4,
        d_head=32, d_ff=256, max_seq_len=64)
    model = tfm.TransformerLM(config)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    actual = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(params))
    assert mfu.transformer_param_count(config) == actual


def test_resnet50_flops_ballpark():
    # torchvision-standard: ~4.09 GMACs fwd at 224 -> ~24.5 GFLOPs
    # per trained image (3x fwd, 2 FLOPs/MAC).
    f = mfu.resnet50_train_flops_per_image(224)
    assert 2.3e10 < f < 2.6e10
    # Quadratic spatial scaling.
    assert mfu.resnet50_train_flops_per_image(112) == pytest.approx(
        f / 4)


def test_transformer_flops_per_token_dominated_by_6n():
    from batch_shipyard_tpu.models import transformer as tfm
    config = tfm.TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=12, n_heads=16,
        d_head=64, d_ff=2816, max_seq_len=2048)
    n = mfu.transformer_param_count(config)
    f = mfu.transformer_train_flops_per_token(config, seq_len=2048)
    assert f > 6 * n
    # Attention term: 6*L*T*d for causal.
    assert f - 6 * n == pytest.approx(
        6 * config.n_layers * 2048 * config.d_model)
    # Non-causal doubles the attention term.
    f_nc = mfu.transformer_train_flops_per_token(
        config, seq_len=2048, causal=False)
    assert f_nc - 6 * n == pytest.approx(2 * (f - 6 * n))


def test_mfu_pct_math_and_unknown_peak():
    # 100 items/s at 1e9 FLOPs/item vs 1 TFLOP/s peak = 10%.
    assert mfu.mfu_pct(100.0, 1e9, 1.0) == pytest.approx(10.0)
    assert mfu.mfu_pct(100.0, 1e9, None) is None
    assert mfu.mfu_pct(100.0, 1e9, 0.0) is None


@pytest.mark.parametrize("kind,gen", [
    ("TPU v2", "v2"),
    ("TPU v3", "v3"),
    ("TPU v4", "v4"),
    ("TPU v5 lite", "v5litepod"),
    ("TPU v5e", "v5litepod"),
    ("TPU v5p", "v5p"),
    ("TPU v6 lite", "v6e"),
    ("TPU v6e", "v6e"),
])
def test_generation_for_device_kind(kind, gen):
    resolved = topology.generation_for_device_kind(kind)
    assert resolved is not None and resolved.name == gen
    assert topology.peak_bf16_tflops_for_device_kind(kind) == \
        resolved.bf16_tflops_per_chip


def test_non_tpu_device_kind_maps_to_none():
    assert topology.generation_for_device_kind("cpu") is None
    assert topology.generation_for_device_kind(
        "NVIDIA A100-SXM4-40GB") is None
    assert topology.peak_bf16_tflops_for_device_kind("cpu") is None


def test_unknown_tpu_device_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        topology.generation_for_device_kind("TPU v5")
    with pytest.raises(ValueError):
        topology.peak_bf16_tflops_for_device_kind("TPU v9x")
