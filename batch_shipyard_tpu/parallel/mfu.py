"""Model-FLOPs-utilization accounting for the training workloads.

The reference publishes raw throughput only (BASELINE.md: images/sec on
16xV100); a TPU framework must also answer "what fraction of the MXU's
peak did that throughput buy?" — the number the scaling-book methodology
tunes against. This module holds the analytic FLOPs models for the two
headline workloads plus the MFU division, with the per-chip peak coming
from parallel/topology.py's generation table keyed on the live
``jax.device_kind``.

Conventions (stated so the denominators are auditable):
- One multiply-accumulate = 2 FLOPs.
- Training step = 3x forward (1 fwd + 2 bwd, the standard accounting).
- Transformer follows the PaLM-appendix formula: 6*N FLOPs per trained
  token for the parameter matmuls (N = params including the tied
  embedding, whose output projection IS a per-token matmul here) plus
  the attention score/value term 12*L*T*d_model, halved for causal
  masking (average visible context T/2).
"""

from __future__ import annotations

from typing import Any, Optional

# torchvision-standard ResNet-50 forward cost at 224x224: 4.09 GMACs.
_RESNET50_FWD_MACS_224 = 4.09e9


def resnet50_train_flops_per_image(image_size: int = 224) -> float:
    """Analytic ResNet-50 training FLOPs per image. Conv cost scales
    with spatial area, so non-224 sizes scale quadratically (exact for
    everything but the fixed-cost final FC, which is <0.1%)."""
    fwd = 2.0 * _RESNET50_FWD_MACS_224 * (image_size / 224.0) ** 2
    return 3.0 * fwd


def transformer_param_count(config: Any) -> int:
    """Parameter count of models/transformer.TransformerLM from its
    config — kept in lockstep with the module tree (embed + per-block
    qkv/out + SwiGLU gate/up/down + RMSNorm scales + final norm; the
    output projection is the tied embedding). Oracle-tested against a
    real ``model.init`` in tests/test_mfu.py so it cannot drift."""
    d, v = config.d_model, config.vocab_size
    h, dh, ff = config.n_heads, config.d_head, config.d_ff
    per_block = (
        3 * d * h * dh        # q, k, v projections
        + h * dh * d          # output projection
        + 3 * d * ff          # SwiGLU gate, up, down
        + 2 * d               # two RMSNorm scales
    )
    return v * d + config.n_layers * per_block + d  # + final norm


def transformer_train_flops_per_token(config: Any, seq_len: int,
                                      causal: bool = True) -> float:
    """PaLM-style FLOPs/token: 6*N for parameter matmuls (fwd 2N +
    bwd 4N) + attention 12*L*T*d (6*L*T*d causal)."""
    n = transformer_param_count(config)
    attn = 12.0 * config.n_layers * seq_len * config.d_model
    if causal:
        attn *= 0.5
    return 6.0 * n + attn


def mfu_pct(items_per_sec_per_chip: float, flops_per_item: float,
            peak_tflops_per_chip: Optional[float]) -> Optional[float]:
    """Achieved model FLOPs as a percentage of one chip's bf16 peak.
    None when the peak is unknown (non-TPU backend) — an absent number
    is honest, a made-up denominator is not."""
    if peak_tflops_per_chip is None or peak_tflops_per_chip <= 0:
        return None
    achieved = items_per_sec_per_chip * flops_per_item
    return 100.0 * achieved / (peak_tflops_per_chip * 1e12)
