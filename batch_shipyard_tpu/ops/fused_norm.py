"""Fused RMSNorm + matmul Pallas kernel: the training-MFU lever for
the transformer's projection matmuls.

Unfused, every block entry costs HBM twice: RMSNorm reads x and writes
the normalized activation, then each projection matmul reads it back
(three times for q/k/v, twice for gate/up). XLA fuses the elementwise
tail of the norm but still materializes the normalized [B*T, d] tensor
between the reduction and the matmuls. This kernel computes the row
rsqrt(mean(x^2)) statistic and the matmul in one VMEM round trip: x is
read once per (m, n) output tile, the normalized rows never touch HBM,
and the matmul accumulates on the MXU in fp32.

The normalization is recomputed per n-tile (VPU work, free next to the
MXU matmul) — the classic flash-attention trade of FLOPs for HBM
bandwidth applied to the norm.

Backward is plain XLA (custom_vjp): the cotangent math is two big
matmuls (dW = n^T g, dn = g W^T) plus the RMSNorm chain rule, all
shapes XLA already schedules well; the win is the forward HBM traffic
(and the [M, d] normalized tensor that no longer needs saving — x is
the only residual).

No reference counterpart: the reference (Azure batch-shipyard) contains
no ML compute; this follows the public fused-norm-projection pattern
(e.g. Megatron-LM's fused layernorm-linear) re-derived for Pallas/TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from batch_shipyard_tpu.ops.quantization import _largest_divisor_block


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    """Reference RMSNorm (fp32 statistics, cast back to x.dtype)."""
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * r * scale.astype(jnp.float32)).astype(x.dtype)


def _fused_kernel(x_ref, s_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # [bm, K]
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    n = x * r * s_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = jax.lax.dot_general(
        n.astype(w_ref.dtype), w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _fused_forward(x, scale, w, eps: float, block_m: int,
                   block_n: int, interpret: bool):
    m, k = x.shape
    n = w.shape[1]
    bm = _largest_divisor_block(m, block_m, align=8)
    bn = _largest_divisor_block(n, block_n)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_fused_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k,), lambda i, j: (0,)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, scale, w)


def _xla_forward(x, scale, w, eps: float):
    return jnp.dot(rmsnorm_ref(x, scale, eps), w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def rmsnorm_matmul(x, scale, w, eps: float = 1e-6,
                   block_m: int = 256, block_n: int = 512,
                   impl: Optional[str] = None):
    """y = (rmsnorm(x) * scale) @ w in one kernel.

    x: [M, K] (callers flatten [B, T, K] to [B*T, K]); scale: [K];
    w: [K, N]. Returns [M, N] in x.dtype with fp32 norm statistics and
    fp32 MXU accumulation.

    impl: 'pallas' | 'xla' | None (pallas on TPU, xla elsewhere —
    same dispatch convention as ops/paged_attention.py).
    """
    return _rmsnorm_matmul_fwd(
        x, scale, w, eps, block_m, block_n, impl)[0]


def _dispatch(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    # Same convention as ops/attention.attention.
    return ("pallas" if jax.default_backend() == "tpu" else "xla")


def _rmsnorm_matmul_fwd(x, scale, w, eps, block_m, block_n, impl):
    mode = _dispatch(impl)
    if mode == "pallas":
        y = _fused_forward(x, scale, w, eps, block_m, block_n,
                           interpret=False)
    elif mode == "interpret":
        y = _fused_forward(x, scale, w, eps, block_m, block_n,
                           interpret=True)
    else:
        y = _xla_forward(x, scale, w, eps)
    return y, (x, scale, w)


def _rmsnorm_matmul_bwd(eps, block_m, block_n, impl, res, g):
    x, scale, w = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    r = jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)  # [M, 1]
    xhat = x32 * r                                          # [M, K]
    n = xhat * scale.astype(jnp.float32)
    dw = jnp.dot(n.T, g32,
                 preferred_element_type=jnp.float32)        # [K, N]
    dn = jnp.dot(g32, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)        # [M, K]
    ds = jnp.sum(xhat * dn, axis=0)                         # [K]
    dxhat = dn * scale.astype(jnp.float32)
    dx = r * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1,
                                      keepdims=True))
    return (dx.astype(x.dtype), ds.astype(scale.dtype),
            dw.astype(w.dtype))


rmsnorm_matmul.defvjp(_rmsnorm_matmul_fwd, _rmsnorm_matmul_bwd)
