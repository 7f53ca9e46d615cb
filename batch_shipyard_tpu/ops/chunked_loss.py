"""Chunked tied-embedding cross-entropy, with a Pallas fused kernel.

The LM loss is the last big HBM consumer in the training step: naive
``logits = hidden @ E.T`` materializes a [B*T, V] fp32 tensor (4 GB at
B=16, T=2048, V=32k) in the forward and again as its cotangent. The
XLA path here (the scan that models/transformer.lm_loss_chunked has
always used) bounds that to one [chunk, V] slab per step; the Pallas
path goes further and never materializes logits in HBM at all:

- forward kernel: grid (T-chunks, V-chunks), online-softmax running
  (max, sumexp, gold-logit) accumulators in VMEM scratch — one MXU
  matmul per tile, only per-token ``lse``/``gold`` vectors leave the
  kernel (flash attention's trick applied to the vocab softmax);
- backward: dlogits = (softmax - onehot) * dscale is recomputed
  tile-by-tile from the saved ``lse`` in TWO kernels — grad_hidden
  accumulates over V-chunks with grid (T, V), grad_embedding over
  T-chunks with grid (V, T) — so each accumulator lives in VMEM for a
  run of consecutive grid steps and logits are never stored.

Convention matches ops/fused_norm.py: impl 'pallas' | 'xla' |
'interpret' | 'auto' (the kernel on a TPU backend when the model dim
is lane-aligned, the XLA scan elsewhere).

No reference counterpart (the reference has no ML compute); the fused
pattern follows public chunked-loss kernels (e.g. Liger) re-derived
for Pallas/TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite -inf stand-in: keeps every intermediate finite (inf - inf is
# nan; exp(-1e30 - m) underflows to exactly 0 for any real m).
_NEG = -1e30


def _pick_v_chunk(d: int) -> int:
    """Vocab tile sized so (E tile + fp32 accumulator) stay well under
    VMEM: ~8 MB combined at the default."""
    if d <= 1024:
        return 512
    if d <= 2048:
        return 256
    return 128


def _fwd_kernel(tgt_ref, h_ref, e_ref, lse_ref, gold_ref,
                m_scr, s_scr, g_scr, *, v_total, v_chunk, n_v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)
        g_scr[...] = jnp.zeros(g_scr.shape, jnp.float32)

    h = h_ref[...].astype(jnp.float32)                    # [bt, D]
    e = e_ref[...].astype(jnp.float32)                    # [bv, D]
    logits = jax.lax.dot_general(
        h, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [bt, bv]
    local = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(vi * v_chunk + local < v_total, logits, _NEG)
    m_prev = m_scr[...]                                   # [bt, 1]
    m_new = jnp.maximum(m_prev,
                        jnp.max(logits, axis=1, keepdims=True))
    s_scr[...] = (s_scr[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(logits - m_new), axis=1,
                            keepdims=True))
    m_scr[...] = m_new
    tgt_local = tgt_ref[...] - vi * v_chunk               # [bt, 1]
    g_scr[...] += jnp.sum(
        jnp.where(local == tgt_local, logits, 0.0), axis=1,
        keepdims=True)

    @pl.when(vi == n_v - 1)
    def _fin():
        lse_ref[...] = m_scr[...] + jnp.log(s_scr[...])
        gold_ref[...] = g_scr[...]


def _dlogits(h_ref, e_ref, tgt_ref, ds_ref, lse_ref, vi, v_total,
             v_chunk):
    """Recompute one [bt, bv] tile of (softmax - onehot) * dscale."""
    h = h_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        h, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    local = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = jnp.where(vi * v_chunk + local < v_total,
                  jnp.exp(logits - lse_ref[...]), 0.0)
    onehot = (local == tgt_ref[...] - vi * v_chunk).astype(
        jnp.float32)
    return (p - onehot) * ds_ref[...]


def _bwd_h_kernel(tgt_ref, ds_ref, lse_ref, h_ref, e_ref, gh_ref,
                  acc, *, v_total, v_chunk, n_v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    dl = _dlogits(h_ref, e_ref, tgt_ref, ds_ref, lse_ref, vi,
                  v_total, v_chunk)                       # [bt, bv]
    acc[...] += jax.lax.dot_general(
        dl, e_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [bt, D]

    @pl.when(vi == n_v - 1)
    def _fin():
        gh_ref[...] = acc[...]


def _bwd_e_kernel(tgt_ref, ds_ref, lse_ref, h_ref, e_ref, ge_ref,
                  acc, *, v_total, v_chunk, n_t):
    vi, ti = pl.program_id(0), pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    dl = _dlogits(h_ref, e_ref, tgt_ref, ds_ref, lse_ref, vi,
                  v_total, v_chunk)                       # [bt, bv]
    acc[...] += jax.lax.dot_general(
        dl, h_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [bv, D]

    @pl.when(ti == n_t - 1)
    def _fin():
        ge_ref[...] = acc[...]


def _pad_rows(x, multiple, fill=0):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _fwd_parts(h2, e, tgt2, v_total, bt, bv, interpret):
    """Run the forward kernel on padded inputs; returns (lse, gold)
    as [N_pad, 1] fp32."""
    n_pad, d = h2.shape
    n_t, n_v = n_pad // bt, e.shape[0] // bv
    kern = functools.partial(_fwd_kernel, v_total=v_total,
                             v_chunk=bv, n_v=n_v)
    return pl.pallas_call(
        kern,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, d), lambda ti, vi: (vi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(tgt2, h2, e)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _xent_pallas(h2, e, tgt, ignore_id, bt, bv, interpret):
    """Mean masked cross-entropy over [N, D] hidden rows (Pallas)."""
    return _xent_pallas_fwd(h2, e, tgt, ignore_id, bt, bv,
                            interpret)[0]


def _xent_pallas_fwd(h2, e, tgt, ignore_id, bt, bv, interpret):
    v_total = e.shape[0]
    n = h2.shape[0]
    hp = _pad_rows(h2, bt)
    tp = _pad_rows(tgt.astype(jnp.int32)[:, None], bt,
                   fill=ignore_id)
    ep = _pad_rows(e, bv)
    lse, gold = _fwd_parts(hp, ep, tp, v_total, bt, bv, interpret)
    mask = (tp != ignore_id).astype(jnp.float32)          # [N_pad, 1]
    count = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum((lse - gold) * mask) / count
    return loss, (h2, e, tgt, lse, mask, count)


def _xent_pallas_bwd(ignore_id, bt, bv, interpret, res, g):
    h2, e, tgt, lse, mask, count = res
    v_total, d = e.shape[0], h2.shape[1]
    hp = _pad_rows(h2, bt)
    tp = _pad_rows(tgt.astype(jnp.int32)[:, None], bt,
                   fill=ignore_id)
    ep = _pad_rows(e, bv)
    n_pad = hp.shape[0]
    n_t, n_v = n_pad // bt, ep.shape[0] // bv
    dscale = (g * mask / count).astype(jnp.float32)       # [N_pad, 1]
    gh = pl.pallas_call(
        functools.partial(_bwd_h_kernel, v_total=v_total, v_chunk=bv,
                          n_v=n_v),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, d), lambda ti, vi: (vi, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(tp, dscale, lse, hp, ep)
    ge = pl.pallas_call(
        functools.partial(_bwd_e_kernel, v_total=v_total, v_chunk=bv,
                          n_t=n_t),
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((bt, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bt, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bt, d), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bv, d), lambda vi, ti: (vi, 0)),
        ],
        out_specs=pl.BlockSpec((bv, d), lambda vi, ti: (vi, 0)),
        out_shape=jax.ShapeDtypeStruct((ep.shape[0], d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        interpret=interpret,
    )(tp, dscale, lse, hp, ep)
    n = h2.shape[0]
    return (gh[:n].astype(h2.dtype), ge[:v_total].astype(e.dtype),
            np.zeros(tgt.shape, jax.dtypes.float0))


_xent_pallas.defvjp(_xent_pallas_fwd, _xent_pallas_bwd)


def _xent_xla(h2, e, tgt, ignore_id, chunk):
    """Scan-chunked XLA path (the historical lm_loss_chunked math):
    one rematerialized [chunk, V] fp32 logits slab at a time."""
    import math as _math

    n = h2.shape[0]
    if n % chunk:
        chunk = _math.gcd(n, chunk) or n
    h_chunks = h2.reshape(n // chunk, chunk, -1)
    t_chunks = tgt.reshape(n // chunk, chunk)

    @jax.checkpoint
    def chunk_nll(h_chunk, t_chunk):
        logits = jnp.einsum(
            "cd,vd->cv", h_chunk.astype(jnp.float32),
            e.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, t_chunk[:, None].astype(jnp.int32),
            axis=-1)[..., 0]
        mask = (t_chunk != ignore_id)
        return (jnp.sum((lse - gold) * mask),
                jnp.sum(mask).astype(jnp.float32))

    def step(carry, xs):
        total, cnt = carry
        nll, k = chunk_nll(*xs)
        return (total + nll, cnt + k), None

    (total, cnt), _ = jax.lax.scan(
        step, (jnp.float32(0.0), jnp.float32(0.0)),
        (h_chunks, t_chunks))
    return total / jnp.maximum(cnt, 1.0)


def resolve_xent_impl(impl: str, d_model: int) -> str:
    """'auto' -> 'pallas' on a TPU backend, 'xla' elsewhere. Either
    Pallas spelling gives way to 'xla' for a lane-misaligned model
    dim (d_model % 128), which the kernel's [bt, D] blocks cannot
    tile."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "interpret", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl != "xla" and d_model % 128:
        return "xla"
    return impl


def chunked_softmax_xent(hidden, embedding, targets,
                         ignore_id: int = -1, impl: str = "auto",
                         chunk_size: int = 128,
                         t_chunk: int = 128,
                         v_chunk: int | None = None):
    """Mean cross-entropy of hidden @ embedding.T against targets,
    without materializing [.., V] logits in HBM.

    hidden: [B, T, D] or [N, D]; embedding: [V, D]; targets matches
    hidden's leading shape. impl: 'pallas' | 'interpret' | 'xla' |
    'auto' (resolve_xent_impl).
    """
    if hidden.ndim == 3:
        hidden = hidden.reshape(-1, hidden.shape[-1])
        targets = targets.reshape(-1)
    impl = resolve_xent_impl(impl, hidden.shape[1])
    if impl in ("pallas", "interpret"):
        bv = v_chunk or _pick_v_chunk(hidden.shape[1])
        return _xent_pallas(hidden, embedding, targets, ignore_id,
                            t_chunk, bv, impl == "interpret")
    return _xent_xla(hidden, embedding, targets, ignore_id,
                     chunk_size)
