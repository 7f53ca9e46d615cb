"""On-chip numeric checks that cannot run in the CPU-forced CI suite.

Run from the repo root on a machine with a chip (the kernel-vs-oracle
harness: every Pallas kernel compiled by Mosaic against its XLA
oracle; exits non-zero if any check fails or raises):

    python tools/tpu_checks.py

Covers the flash-ring path: the
3-case rotation switch + logsumexp merge of
ops/ring_attention.ring_attention_virtual_shards — the same code the
shard_map ring body executes per rotation — against the dense oracle,
forward AND backward, at unit input scale, on the real chip.

Pallas interpret mode aborts inside shard_map on CPU, so CI covers the
building blocks in interpret mode only; this harness is the real-MXU
validation. fp32 cases run under ``jax.default_matmul_precision(
'highest')`` so their comparisons are meaningful (the TPU default is
bf16-pass matmuls, ~1e-3 relative). That setting is SCOPED to them: it
reaches the dots inside Pallas kernels too, and Mosaic refuses a
bf16 or int8 dot at fp32 contract precision ("Bad lhs type") — which
is how a process-wide 'highest' failed every non-fp32 kernel here.
"""

from __future__ import annotations

import json
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np


def _exact_if_fp32(dtype):
    """fp32 cases compare at 'highest' matmul precision; bf16/int8
    cases must not (see the module docstring)."""
    if dtype == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def _rel(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-30))


def check_flash_ring_virtual_shards() -> bool:
    from batch_shipyard_tpu.ops import attention as attn
    from batch_shipyard_tpu.ops import ring_attention as ring

    all_ok = True
    rng = np.random.RandomState(3)
    shape = (1, 512, 2, 64)  # unit scale: no atol masking
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    k = jnp.asarray(rng.randn(*shape), jnp.float32)
    v = jnp.asarray(rng.randn(*shape), jnp.float32)

    with jax.default_matmul_precision("highest"):
        for causal in (True, False):
            for sp in (2, 4):
                def loss_ring(q, k, v):
                    return jnp.sum(ring.ring_attention_virtual_shards(
                        q, k, v, sp=sp, causal=causal) ** 2)

                def loss_ref(q, k, v):
                    return jnp.sum(attn.mha_reference(
                        q, k, v, causal=causal) ** 2)

                out_ring = jax.jit(
                    lambda q, k, v: ring.ring_attention_virtual_shards(
                        q, k, v, sp=sp, causal=causal))(q, k, v)
                out_ref = attn.mha_reference(q, k, v, causal=causal)
                rel_f = (np.linalg.norm(np.asarray(out_ring - out_ref)) /
                         np.linalg.norm(np.asarray(out_ref)))
                g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
                    q, k, v)
                g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(
                    q, k, v)
                rels = []
                for a, b in zip(g_ring, g_ref):
                    a, b = np.asarray(a), np.asarray(b)
                    rels.append(np.linalg.norm(a - b) /
                                max(np.linalg.norm(b), 1e-30))
                ok = rel_f < 1e-4 and all(r < 5e-4 for r in rels)
                print(f"flash-ring sp={sp} causal={causal}: "
                      f"fwd_rel={rel_f:.2e} "
                      f"grad_rels={[f'{r:.2e}' for r in rels]} "
                      f"{'OK' if ok else 'FAIL'}")
                all_ok = all_ok and ok
    return all_ok


def check_flash_single_chip() -> bool:
    """flash_attention (Pallas fwd+bwd kernels) vs the dense oracle on
    the real MXU — the single-chip kernel the training path runs — at
    fp32 T=1024 and at the smoke's training shape (bf16, 16 heads,
    T=2048)."""
    from batch_shipyard_tpu.ops import attention as attn

    all_ok = True
    for label, dtype, shape, tol_f, tol_g in (
            ("f32 T1024 h4", jnp.float32, (2, 1024, 4, 64), 1e-4,
             5e-4),
            ("bf16 T2048 h16", jnp.bfloat16, (2, 2048, 16, 64), 2e-2,
             4e-2)):
        with _exact_if_fp32(dtype):
            rng = np.random.RandomState(7)
            q = jnp.asarray(rng.randn(*shape), dtype)
            k = jnp.asarray(rng.randn(*shape), dtype)
            v = jnp.asarray(rng.randn(*shape), dtype)
            for causal in (True, False):
                out = jax.jit(lambda q, k, v: attn.flash_attention(
                    q, k, v, causal))(q, k, v)
                ref = attn.mha_reference(q, k, v, causal=causal)
                rel_f = _rel(out, ref)

                def loss_flash(q, k, v):
                    return jnp.sum(attn.flash_attention(
                        q, k, v, causal).astype(jnp.float32) ** 2)

                def loss_ref(q, k, v):
                    return jnp.sum(attn.mha_reference(
                        q, k, v, causal=causal).astype(jnp.float32) ** 2)

                g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(
                    q, k, v)
                g_rf = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(
                    q, k, v)
                rels = [_rel(a, b) for a, b in zip(g_fl, g_rf)]
                ok = rel_f < tol_f and all(r < tol_g for r in rels)
                print(f"flash single-chip [{label}] causal={causal}: "
                      f"fwd_rel={rel_f:.2e} "
                      f"grad_rels={[f'{r:.2e}' for r in rels]} "
                      f"{'OK' if ok else 'FAIL'}")
                all_ok = all_ok and ok
    return all_ok


# Paged-decode shape sets: the historical small case, and the shapes
# chip_smoke.py serves at (bf16, 16 heads x 64, page 64) — a kernel
# Mosaic accepts at one and refuses at the other has happened.
# (label, dtype, heads, page, tolerance vs the XLA oracle)
_PAGED_CASES = (
    ("f32 h4 page16", jnp.float32, 4, 16, 1e-4),
    ("bf16 h16 page64", jnp.bfloat16, 16, 64, 2e-2),
)


def _folded(pool):
    """[P, page, H, D] -> the pool as the serving cache stores it and
    the kernel blocks it, [P, page, H*D]."""
    return pool.reshape(*pool.shape[:2], -1)


def _paged_case(rng, heads, page, batch=8, depth=64, max_blocks=8):
    num_pages = batch * max_blocks + 1
    q = jnp.asarray(rng.randn(batch, 1, heads, depth), jnp.float32)
    k_f = jnp.asarray(
        rng.randn(num_pages, page, heads, depth), jnp.float32)
    v_f = jnp.asarray(
        rng.randn(num_pages, page, heads, depth), jnp.float32)
    # Distinct random pages per slot; ragged lengths incl. 1 and full.
    perm = rng.permutation(num_pages)[:batch * max_blocks]
    table = jnp.asarray(perm.reshape(batch, max_blocks), jnp.int32)
    lengths = jnp.asarray(
        [1, 5, page, page + 1, 3 * page - 2, 4 * page,
         max_blocks * page - 1, max_blocks * page], jnp.int32)
    return q, k_f, v_f, table, lengths


def _served_mha_case(rng, slots=48, heads=32, depth=128, page=64,
                     entries=32, pool=193, parked=1):
    """Baichuan's decode attention as its cell serves it: 48 slots,
    32 heads of 128, 193 pages of 64 behind tables of 32 entries,
    bfloat16; a third of the slots seated at contexts of 1 to 2,048
    mixed (a chunk's edge at 128 keys from both sides, the cell's mean,
    a full table), the rest parked on the scratch page at length
    ``parked``: 1 as a parked cursor left them until PR 44, 0 as the
    step's live mask hands them over since."""
    seated = [2048, 1337, 580, 129, 128, 127, 65, 64, 63, 2, 1, 911,
              400, 257, 256, 33]
    lengths = np.full(slots, parked, np.int32)
    lengths[::3] = seated
    q = jnp.asarray(rng.randn(slots, 1, heads, depth), jnp.bfloat16)
    k_p = jnp.asarray(rng.randn(pool, page, heads * depth),
                      jnp.bfloat16)
    v_p = jnp.asarray(rng.randn(pool, page, heads * depth),
                      jnp.bfloat16)
    table = np.zeros((slots, entries), np.int32)
    ids = iter(rng.permutation(pool - 1) + 1)
    for b, length in enumerate(lengths):
        if b % 3 == 0:
            pages = -(-int(length) // page)
            table[b, :pages] = [next(ids) for _ in range(pages)]
    return q, k_p, v_p, jnp.asarray(table), jnp.asarray(lengths)


def check_paged_attention() -> bool:
    """The paged-decode kernel of bf16/f32 pages as the dispatch picks
    it on a TPU (one program a slot over its live pages: an MHA pool
    is the grouped kernel's case of as many K/V heads as query heads)
    vs the XLA gather oracle with random block tables and ragged
    lengths — the serving engine's headline kernel — and at
    Baichuan's served shape, 4,096 channels wide, 2 pages a chunk,
    its 32 parked slots at length 1 and masked to length 0 (zeros in
    their rows on both roads)."""
    from batch_shipyard_tpu.ops import paged_attention as paged

    kernel = jax.jit(functools.partial(paged.paged_decode_attention,
                                       impl="kernel"))
    all_ok = True
    cases = []
    for label, dtype, heads, page, tol in _PAGED_CASES:
        q, k_f, v_f, table, lengths = _paged_case(
            np.random.RandomState(11), heads, page)
        q, k_p, v_p = (x.astype(dtype)
                       for x in (q, _folded(k_f), _folded(v_f)))
        cases.append((label, dtype, tol, (q, k_p, v_p, table, lengths)))
    for parked in (1, 0):
        cases.append((
            f"bf16 h32x128 page64 table32 slots48, 32 parked at "
            f"{parked} (baichuan)", jnp.bfloat16, 2e-2,
            _served_mha_case(np.random.RandomState(12), parked=parked)))
    for label, dtype, tol, operands in cases:
        with _exact_if_fp32(dtype):
            out_k = kernel(*operands)
            out_x = paged.paged_decode_attention_xla(*operands)
            rel = _rel(out_k, out_x)
            empty = np.asarray(operands[-1]) == 0
            ok = rel < tol and not any(
                np.asarray(out, np.float32)[empty].any()
                for out in (out_k, out_x))
            print(f"paged-attention kernel vs xla [{label}]: "
                  f"rel={rel:.2e} {'OK' if ok else 'FAIL'}")
            all_ok = all_ok and ok
    return all_ok


# (label, query heads, K/V heads, query positions a slot, table
# entries, window, visible block, whether every other slot's second
# block is dead): the served calls whose programs hand a first chunk
# on (tools/paged_decode_timing.py's STEP_CALLS beside Baichuan's
# one-token MHA call), heads of 128, pages of 64, bfloat16
_HANDOVER_CALLS = (
    ("baichuan h32 mha", 32, 32, 1, 32, 0, 1, False),
    ("kexaone h64/8 x2 full", 64, 8, 2, 128, 0, 1, False),
    ("kexaone h64/8 x2 ring4 w128", 64, 8, 2, 4, 128, 1, False),
    ("sdar h32/4 x4 all keys", 32, 4, 4, 129, 0, 4, False),
    ("sdar h32/4 x8 two blocks, some dead", 32, 4, 8, 129, 0, 4, True),
)


def check_paged_handover() -> bool:
    """The one-program-a-slot kernel's hand-over between programs on
    the chip: seated slots of one to five chunks of pages with runs
    of parked slots (length 0) before, between and behind them. Each
    seated slot's first chunk is started by the seated slot before it
    and passed on by the parked programs between; its row must be BIT
    FOR BIT what the slot gives alone (a batch of one starts its own
    chunk 0, as every slot did until PR 48), the parked rows zero,
    and the call within bfloat16's rounding of the XLA gather."""
    from batch_shipyard_tpu.ops import paged_attention as paged

    depth, page, all_ok = 128, 64, True
    for label, heads, kv_heads, positions, entries, window, block, \
            some_dead in _HANDOVER_CALLS:
        rng = np.random.RandomState(13)
        width = kv_heads * depth
        keys = paged.gqa_chunk_pages(page, width, 2, entries) * page
        # chunks a seated slot: 1, 2, 1, 3, 5, 1, 1, 2 (the buffer
        # half flips between neighbours or does not)
        lengths = np.asarray(
            [0, 0, keys, 0, keys + 1, 9, 0, 0, 0, 2 * keys + 70,
             5 * keys, keys - 1, 0, positions, 2 * keys, 0], np.int32)
        need = np.minimum(-(-lengths // page), entries)
        pool = 1 + int(need.sum())
        q = jnp.asarray(rng.randn(len(lengths), positions, heads, depth),
                        jnp.bfloat16)
        k_p, v_p = (jnp.asarray(rng.randn(pool, page, width),
                                jnp.bfloat16) for _ in range(2))
        table = np.zeros((len(lengths), entries), np.int32)
        ids = iter(rng.permutation(pool - 1) + 1)
        for b, pages in enumerate(need):
            table[b, :pages] = [next(ids) for _ in range(pages)]
        table, lengths = jnp.asarray(table), jnp.asarray(lengths)
        live = jnp.where(jnp.arange(len(lengths)) % 2 == 0, positions,
                         block) if some_dead else None
        at = (lambda rows: {"live_positions": live[rows]}) \
            if some_dead else (lambda rows: {})
        kernel = jax.jit(functools.partial(
            paged.paged_decode_attention, impl="kernel", window=window,
            block=block))
        out_k = kernel(q, k_p, v_p, table, lengths, **at(slice(None)))
        out_x = paged.paged_decode_attention(
            q, k_p, v_p, table, lengths, impl="xla", window=window,
            block=block, **at(slice(None)))
        rel = _rel(out_k, out_x)
        seated = np.flatnonzero(np.asarray(lengths) > 0)
        alone = all(
            np.array_equal(
                np.asarray(out_k[b], np.float32),
                np.asarray(kernel(q[b:b + 1], k_p, v_p, table[b:b + 1],
                                  lengths[b:b + 1],
                                  **at(slice(b, b + 1)))[0], np.float32))
            for b in seated)
        zeros = not np.delete(np.asarray(out_k, np.float32), seated,
                              axis=0).any()
        ok = rel < 2e-2 and alone and zeros
        print(f"paged hand-over [{label}]: rel={rel:.2e} "
              f"alone-bitwise={alone} parked-zero={zeros} "
              f"{'OK' if ok else 'FAIL'}")
        all_ok = all_ok and ok
    return all_ok


def check_latent_paged_decode() -> bool:
    """The latent pool's kernel (mla_paged_decode) on the chip at the
    published row (640 lanes: 512 of compressed vector, 64 of rotary
    key, zeros), 128 heads, one and two query positions a slot:
    seated slots of one to five chunks of pages with parked slots
    before, between and behind them. Each seated slot's rows BIT FOR
    BIT what the slot gives alone (the hand-over starts its first
    chunk behind another slot's last), the parked rows zero, and the
    call within bfloat16's rounding of the XLA gather."""
    from batch_shipyard_tpu.ops import paged_attention as paged

    heads, lanes, value, page, entries, all_ok = 128, 640, 512, 64, 48, True
    kw = dict(value_lanes=value, scale=192 ** -0.5)
    for positions in (1, 2):
        rng = np.random.RandomState(17)
        keys = paged.gqa_chunk_pages(page, lanes, 2, entries) * page
        lengths = np.asarray(
            [0, keys, 0, keys + 1, 9, 0, 0, 2 * keys + 70, 5 * keys,
             keys - 1, 0, positions, 0], np.int32)
        need = np.minimum(-(-lengths // page), entries)
        pool = 1 + int(need.sum())
        q = jnp.asarray(0.1 * rng.randn(len(lengths), positions, heads,
                                        lanes), jnp.bfloat16)
        rows = jnp.asarray(rng.randn(pool, page, lanes), jnp.bfloat16)
        table = np.zeros((len(lengths), entries), np.int32)
        ids = iter(rng.permutation(pool - 1) + 1)
        for b, pages in enumerate(need):
            table[b, :pages] = [next(ids) for _ in range(pages)]
        table, lengths = jnp.asarray(table), jnp.asarray(lengths)
        kernel = jax.jit(functools.partial(
            paged.mla_paged_decode_attention, impl="kernel", **kw))
        out_k = kernel(q, rows, table, lengths)
        out_x = paged.mla_paged_decode_attention(
            q, rows, table, lengths, impl="xla", **kw)
        rel = _rel(out_k, out_x)
        seated = np.flatnonzero(np.asarray(lengths) > 0)
        alone = all(
            np.array_equal(
                np.asarray(out_k[b], np.float32),
                np.asarray(kernel(q[b:b + 1], rows, table[b:b + 1],
                                  lengths[b:b + 1])[0], np.float32))
            for b in seated)
        zeros = not np.delete(np.asarray(out_k, np.float32), seated,
                              axis=0).any()
        ok = rel < 2e-2 and alone and zeros
        print(f"latent paged decode [{positions} positions]: "
              f"rel={rel:.2e} alone-bitwise={alone} parked-zero={zeros} "
              f"{'OK' if ok else 'FAIL'}")
        all_ok = all_ok and ok
    return all_ok


def check_int8_matmul() -> bool:
    """quantize_int8 + int8_matmul on the real MXU: the quantized
    product must sit within the per-element quantization error bound
    of the fp32 product."""
    from batch_shipyard_tpu.ops import quantization as qz

    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.randn(256, 512), jnp.float32)
    w = jnp.asarray(rng.randn(512, 384) / 22.6, jnp.float32)
    out = jax.jit(qz.quantized_linear)(x, w)
    with jax.default_matmul_precision("highest"):
        ref = x @ w
    rel = (np.linalg.norm(np.asarray(out - ref)) /
           np.linalg.norm(np.asarray(ref)))
    # int8 per-row absmax: ~0.5/127 relative per operand; the matmul
    # contraction averages error down — 2% relative is generous.
    ok = rel < 0.02
    print(f"int8 quantized_linear vs fp32: rel={rel:.2e} "
          f"{'OK' if ok else 'FAIL'}")
    return ok


def check_fused_norm() -> bool:
    """Pallas fused RMSNorm+matmul vs the unfused XLA composition on
    the real chip (fwd; bwd is shared XLA code)."""
    from batch_shipyard_tpu.ops import fused_norm as fn

    rng = np.random.RandomState(17)
    x = jnp.asarray(rng.randn(512, 1024), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.randn(1024), jnp.float32)
    w = jnp.asarray(rng.randn(1024, 1536) / 32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda x, s, w: fn.rmsnorm_matmul(
            x, s, w, impl="pallas"))(x, scale, w)
        ref = jax.jit(lambda x, s, w: fn.rmsnorm_matmul(
            x, s, w, impl="xla"))(x, scale, w)
    rel = (np.linalg.norm(np.asarray(out - ref)) /
           np.linalg.norm(np.asarray(ref)))
    ok = rel < 1e-4
    print(f"fused rmsnorm_matmul pallas vs xla: rel={rel:.2e} "
          f"{'OK' if ok else 'FAIL'}")
    return ok


# Check name -> callable.
CHECKS = {
    "flash_single_chip": check_flash_single_chip,
    "flash_ring": check_flash_ring_virtual_shards,
    "paged_attention": check_paged_attention,
    "paged_handover": check_paged_handover,
    "latent_paged_decode": check_latent_paged_decode,
    "int8_matmul": check_int8_matmul,
    "fused_norm": check_fused_norm,
    "chunked_cross_entropy": None,  # bound below (round-5 kernel)
}


def check_chunked_cross_entropy() -> bool:
    """Pallas chunked cross-entropy vs the XLA chunked loss on the
    real chip (fwd + grad wrt hidden/embedding), small and at the
    smoke's widths (d_model 1024, vocab 32000, bf16 hidden)."""
    from batch_shipyard_tpu.ops import chunked_loss as cl

    all_ok = True
    for label, h_dtype, (batch, t_len, d, vocab), tol_f, tol_g in (
            ("f32 d128 v1024", jnp.float32, (2, 256, 128, 1024),
             1e-5, 1e-4),
            ("bf16 d1024 v32000", jnp.bfloat16, (2, 1024, 1024, 32000),
             1e-4, 2e-2)):
        with jax.default_matmul_precision("highest"):
            rng = np.random.RandomState(19)
            hidden = jnp.asarray(rng.randn(batch, t_len, d), h_dtype)
            embed = jnp.asarray(rng.randn(vocab, d) / d ** 0.5,
                                jnp.float32)
            targets = jnp.asarray(rng.randint(0, vocab, (batch, t_len)),
                                  jnp.int32)
            targets = targets.at[0, :7].set(-1)  # exercise the ignore mask

            def loss_pl(h, e):
                return cl.chunked_softmax_xent(h, e, targets,
                                               impl="pallas")

            def loss_ref(h, e):
                return cl.chunked_softmax_xent(h, e, targets, impl="xla")

            out = jax.jit(loss_pl)(hidden, embed)
            ref = jax.jit(loss_ref)(hidden, embed)
            rel_f = abs(float(out - ref)) / max(abs(float(ref)), 1e-30)
            g_pl = jax.jit(jax.grad(loss_pl, argnums=(0, 1)))(hidden,
                                                              embed)
            g_rf = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(hidden,
                                                               embed)
            rels = [_rel(a, b) for a, b in zip(g_pl, g_rf)]
            ok = rel_f < tol_f and all(r < tol_g for r in rels)
            print(f"chunked cross-entropy pallas vs xla [{label}]: "
                  f"fwd_rel={rel_f:.2e} "
                  f"grad_rels={[f'{r:.2e}' for r in rels]} "
                  f"{'OK' if ok else 'FAIL'}")
            all_ok = all_ok and ok
    return all_ok


def check_paged_attention_int8() -> bool:
    """int8-page paged decode: the Pallas in-kernel dequant vs the
    XLA gathered-slice dequant, and both vs the fp pages the int8 was
    quantized from (quantization-noise bound)."""
    from batch_shipyard_tpu.ops import paged_attention as paged
    from batch_shipyard_tpu.ops.quantization import quantize_int8_rows

    all_ok = True
    for label, dtype, heads, page, tol in _PAGED_CASES:
        with _exact_if_fp32(dtype):
            q, k_f, v_f, table, lengths = _paged_case(
                np.random.RandomState(31), heads, page)
            q = q.astype(dtype)
            kp, ks = quantize_int8_rows(k_f)
            vp, vs = quantize_int8_rows(v_f)
            kp, vp, k_f, v_f = map(_folded, (kp, vp, k_f, v_f))
            out_k = jax.jit(paged.paged_decode_attention_kernel)(
                q, kp, vp, table, lengths, ks, vs)
            out_x = paged.paged_decode_attention_xla(
                q, kp, vp, table, lengths, k_scales=ks, v_scales=vs)
            ref = paged.paged_decode_attention_xla(
                q, k_f.astype(dtype), v_f.astype(dtype), table, lengths)
            rel_kx = _rel(out_k, out_x)
            rel_fp = _rel(out_x, ref)
            ok = rel_kx < tol and rel_fp < 0.03
            print(f"paged-attention int8 kernel vs xla [{label}]: "
                  f"rel={rel_kx:.2e}; int8 vs fp pages: rel={rel_fp:.2e} "
                  f"{'OK' if ok else 'FAIL'}")
            all_ok = all_ok and ok
    return all_ok


def check_int8_kv_dequant_fusion() -> bool:
    """ADVICE r5: the dense int8 KV decode path
    (models/transformer._decode_attend) dequantizes the full
    [B, T, H, D] cache with an elementwise multiply OUTSIDE any
    kernel and relies on XLA fusing it into the two attention dots.
    If the compiler materializes the dequantized k_all/v_all instead,
    peak HBM exceeds the bf16 cache the int8 path claims to halve.
    Correctness is unaffected either way — this check inspects the
    COMPILED step's buffer assignment: temp-buffer bytes must stay
    well below one dequantized cache tensor."""
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import transformer as tfm

    batch, t_len, heads, depth = 8, 2048, 4, 64
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=heads * depth, n_layers=1,
        n_heads=heads, d_head=depth, d_ff=512, dtype=jnp.bfloat16,
        kv_cache_dtype="int8")
    dcfg = inf.decode_config(cfg, t_len)
    model = tfm.TransformerLM(dcfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((1,), jnp.int32))["params"]
    cache = inf.init_cache(model, params, batch)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    positions = jnp.zeros((batch,), jnp.int32)

    def step(params, cache, tokens, positions):
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions[:, None], mutable=["cache"])
        return logits, mutated["cache"]

    compiled = jax.jit(step).lower(params, cache, tokens,
                                   positions).compile()
    mem = compiled.memory_analysis()
    temp = getattr(mem, "temp_size_in_bytes", None)
    if temp is None:
        raise RuntimeError(
            "compiled.memory_analysis() has no temp_size_in_bytes on "
            "this backend — fusion cannot be verified")
    # One dequantized cache tensor (K or V) in bf16. A fused step's
    # temps are dominated by the [B, H, 1, T] fp32 scores (~0.25 MB
    # here); materializing even ONE full dequantized cache adds 8 MB.
    dequant_bytes = batch * t_len * heads * depth * 2
    ok = temp < dequant_bytes
    verdict = ("OK" if ok else
               "FAIL — the dense int8 path is materializing the "
               "dequantized cache")
    print(f"int8 KV dequant fusion: temp_bytes={temp} "
          f"(dequantized-cache threshold {dequant_bytes}) {verdict}")
    return ok


def check_ring_collectives() -> bool:
    """Async-DMA ring collectives (ops/ring_collectives.py): the
    virtual-ring kernels COMPILED on the chip — the same Mosaic
    DMA/semaphore lowering the multi-chip remote-copy kernels use —
    vs the dense references, and, when more than one TPU device is
    attached, the real shard_map remote-DMA ring vs the lax
    collectives. This check gates ring_attention's impl='pallas_dma'
    tier (resolve_ring_impl)."""
    from batch_shipyard_tpu.ops import ring_collectives as rc
    from batch_shipyard_tpu.parallel import mesh as mesh_mod

    all_ok = True
    rng = np.random.RandomState(23)
    for ring in (2, 4):
        x = jnp.asarray(rng.randn(ring, 128, 128), jnp.float32)
        got = jax.jit(rc.ring_all_gather_virtual)(x)
        ref = x.reshape(ring * 128, 128)
        rel_ag = max(
            float(np.linalg.norm(np.asarray(got[i]) - np.asarray(ref))
                  / np.linalg.norm(np.asarray(ref)))
            for i in range(ring))
        y = jnp.asarray(rng.randn(ring, ring * 128, 128), jnp.float32)
        got_rs = jax.jit(rc.ring_reduce_scatter_virtual)(y)
        ref_rs = jnp.sum(y, axis=0).reshape(ring, 128, 128)
        rel_rs = (np.linalg.norm(np.asarray(got_rs - ref_rs)) /
                  np.linalg.norm(np.asarray(ref_rs)))
        ok = rel_ag < 1e-6 and rel_rs < 1e-5
        print(f"ring-collectives virtual ring={ring}: "
              f"ag_rel={rel_ag:.2e} rs_rel={rel_rs:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        all_ok = all_ok and ok
    n_dev = len(jax.devices())
    if n_dev > 1 and jax.default_backend() == "tpu":
        mesh = mesh_mod.make_mesh(
            mesh_mod.auto_axis_sizes(n_dev, sp=n_dev))
        x = jnp.asarray(rng.randn(n_dev * 128, 128), jnp.float32)
        got = jax.jit(lambda x: rc.ring_all_gather(x, mesh, "sp"))(x)
        rel_ag = (np.linalg.norm(np.asarray(got - x)) /
                  np.linalg.norm(np.asarray(x)))
        y = jnp.asarray(rng.randn(n_dev, n_dev * 128, 128),
                        jnp.float32)
        got_rs = jax.jit(
            lambda y: rc.ring_reduce_scatter(y, mesh, "sp"))(y)
        ref_rs = jnp.sum(y, axis=0)
        rel_rs = (np.linalg.norm(np.asarray(got_rs - ref_rs)) /
                  np.linalg.norm(np.asarray(ref_rs)))
        ok = rel_ag < 1e-6 and rel_rs < 1e-5
        print(f"ring-collectives remote-DMA ring={n_dev}: "
              f"ag_rel={rel_ag:.2e} rs_rel={rel_rs:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        all_ok = all_ok and ok
    else:
        print("ring-collectives remote-DMA: skipped "
              f"({n_dev} device(s) — virtual kernels only)")
    return all_ok


def check_dense_decode_int8() -> bool:
    """In-kernel int8 dense decode (ops/decode_attention.py): the
    Pallas kernel vs the XLA dequant+einsum oracle, and both vs the
    fp cache the int8 was quantized from (quantization-noise bound),
    over ragged lengths including the masked short-prefix region, at
    4 heads fp32 and at the smoke width (16 heads, bf16 queries)."""
    from batch_shipyard_tpu.ops import decode_attention as dd
    from batch_shipyard_tpu.ops.quantization import quantize_int8_rows

    all_ok = True
    for label, dtype, heads, tol in (("f32 h4", jnp.float32, 4, 1e-4),
                                     ("bf16 h16", jnp.bfloat16, 16,
                                      2e-2)):
        with _exact_if_fp32(dtype):
            rng = np.random.RandomState(37)
            batch, t_len, depth = 8, 512, 64
            q = jnp.asarray(rng.randn(batch, 1, heads, depth), dtype)
            k_f = jnp.asarray(rng.randn(batch, t_len, heads, depth),
                              jnp.float32)
            v_f = jnp.asarray(rng.randn(batch, t_len, heads, depth),
                              jnp.float32)
            ck, ks = quantize_int8_rows(k_f)
            cv, vs = quantize_int8_rows(v_f)
            lengths = jnp.asarray(
                [1, 5, 128, 129, 300, 511, 512, 64], jnp.int32)
            out_k = jax.jit(dd.dense_decode_attention_kernel)(
                q, ck, cv, ks, vs, lengths)
            out_x = dd.dense_decode_attention_xla(q, ck, cv, ks, vs,
                                                  lengths)
            fp_scales = jnp.ones((batch, t_len, heads), jnp.float32)
            ref = dd.dense_decode_attention_xla(
                q, k_f, v_f, fp_scales, fp_scales, lengths)
            rel_kx = _rel(out_k, out_x)
            rel_fp = _rel(out_x, ref)
            ok = rel_kx < tol and rel_fp < 0.03
            print(f"dense-decode int8 kernel vs xla [{label}]: "
                  f"rel={rel_kx:.2e}; int8 vs fp cache: rel={rel_fp:.2e} "
                  f"{'OK' if ok else 'FAIL'}")
            all_ok = all_ok and ok
    return all_ok


def check_dense_decode_hlo() -> bool:
    """The 2x-HBM claim, verified not hoped: compile the dense int8
    decode step with the in-kernel impl and assert on the COMPILED
    artifact that (a) the Pallas kernel custom-call is present and
    (b) no full-cache-sized f32/bf16 dequant buffer exists anywhere
    in the HLO — HBM holds int8 + scales only."""
    import re

    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import transformer as tfm

    batch, t_len, heads, depth = 8, 2048, 4, 64
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=heads * depth, n_layers=1,
        n_heads=heads, d_head=depth, d_ff=512, dtype=jnp.bfloat16,
        kv_cache_dtype="int8", decode_attention_impl="kernel")
    dcfg = inf.decode_config(cfg, t_len)
    model = tfm.TransformerLM(dcfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((1,), jnp.int32))["params"]
    cache = inf.init_cache(model, params, batch)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    positions = jnp.zeros((batch,), jnp.int32)

    def step(params, cache, tokens, positions):
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions[:, None], mutable=["cache"])
        return logits, mutated["cache"]

    compiled = jax.jit(step).lower(params, cache, tokens,
                                   positions).compile()
    hlo = compiled.as_text()
    # The Pallas kernel must actually be in the program — match the
    # Mosaic lowering target specifically (a generic 'custom-call'
    # string also matches sharding-annotation custom-calls).
    has_kernel = ("tpu_custom_call" in hlo or "MosaicKernel" in hlo)
    cache_elems = batch * t_len * heads * depth
    dequant_buffers = []
    for dtype_name, dims in re.findall(
            r"(f32|bf16)\[([0-9,]+)\]", hlo):
        sizes = [int(d) for d in dims.split(",") if d]
        # Element count alone bounds this (no dim-count filter: a
        # reshaped 2-D materialization of the dequantized cache is
        # just as fatal as a 4-D one).
        if sizes and np.prod(sizes) >= cache_elems:
            dequant_buffers.append(f"{dtype_name}[{dims}]")
    ok = has_kernel and not dequant_buffers
    print(f"dense-decode HLO: kernel_custom_call={has_kernel} "
          f"full-cache fp buffers={sorted(set(dequant_buffers))} "
          f"{'OK' if ok else 'FAIL'}")
    return ok


CHECKS["chunked_cross_entropy"] = check_chunked_cross_entropy
CHECKS["paged_attention_int8"] = check_paged_attention_int8
CHECKS["int8_kv_dequant_fusion"] = check_int8_kv_dequant_fusion
CHECKS["ring_collectives"] = check_ring_collectives
CHECKS["dense_decode_int8"] = check_dense_decode_int8
CHECKS["dense_decode_hlo"] = check_dense_decode_hlo


def run_all() -> dict:
    """Run every check, returning {name: {ok, error?, backend}}. A
    check that raises (Mosaic refusing a kernel, say) is recorded with
    its error text and the run goes on, so one call reports every
    kernel's outcome."""
    import traceback

    backend = jax.default_backend()
    print(f"backend={backend} devices={jax.devices()}")
    results: dict = {}
    for name, fn in CHECKS.items():
        try:
            ok = bool(fn())
            results[name] = {"ok": ok, "backend": backend}
        except Exception as exc:  # noqa: BLE001 - record, keep going
            traceback.print_exc()
            results[name] = {"ok": False, "backend": backend,
                             "error": f"{type(exc).__name__}: {exc}"}
            print(f"{name}: EXCEPTION {exc}")
    n_ok = sum(1 for r in results.values() if r["ok"])
    print(f"{n_ok}/{len(results)} TPU checks OK"
          + ("" if n_ok < len(results) else " — ALL TPU CHECKS OK"))
    return results


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json-out", metavar="PATH", default=None,
        help="also write the per-check results as JSON (a record of "
             "the run; nothing reads it back)")
    args = parser.parse_args(argv)
    results = run_all()
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(results, fh, indent=2)
    return 0 if all(r["ok"] for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
