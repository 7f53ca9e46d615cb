"""Continuous batching: a slot-based serving engine over the KV-cache
decode path.

ROADMAP item (the reference has no serving story): instead of
generating whole batches in lockstep (models/inference.generate —
every sequence must finish before any slot frees), the engine holds a
fixed pool of decode SLOTS sharing one batched KV cache. Requests
admit into free slots as they arrive (per-slot prefill via a batch-1
scatter into the big cache), every engine step decodes ONE token for
all active slots in a single jitted call, and finished slots free
immediately for the next request — the throughput property
continuous-batching servers (Orca/vLLM-class) are built around.

TPU-first mechanics: the per-slot cache index ([B] int32,
transformer._decode_attend) lets slots sit at different depths in one
[B, T, H, D] cache; per-slot RoPE positions ride the 2-D positions
path; everything is static-shape jitted — admit/emit bookkeeping is
host-side Python, compute is two compiled functions (prefill, step).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import itertools
import json
import os
import random
import resource
import threading
import time
import uuid
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import kv_pages
from batch_shipyard_tpu.models import moe
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.trace import spans as trace_spans
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)

# The leaf phases of one engine step, in the order a step runs them
# (docs/32-tracing.md): each is a ``serve:<phase>`` annotation in a
# profiler trace and a ``<phase>_ms`` attr of the step's row.
STEP_PHASES = ("admit", "prefill", "slot_update", "grow_pages",
               "dispatch", "readback", "emit")
# The annotations' common prefix. Whoever drives the engine puts its
# own wait for work under it as ``serve:no_work`` (server.py), which
# is no phase of a step.
PHASE_PREFIX = "serve:"
# Why a decode step in flight was read back BEFORE its successor was
# dispatched (ContinuousBatcher._settle), so that the successor did
# not overlap it: a dry pool, a cancel, a drain, or nothing left to
# dispatch. An admission is none: its prefill is dispatched behind
# the step in flight (_admit).
SETTLE_CAUSES = ("preempt", "cancel", "drain", "idle")
# The two kinds of launch the engine lands (Launch.kind): a decode
# step (a draft/verify block of the speculative engine is one) and a
# prefill with its seat program.
LAUNCH_KINDS = ("decode", "prefill")
# A landing whose launch held the head of the device's queue for
# longer than this writes a serve_stall record (_stalled); a call of
# step() of which more than a quarter of it lies under no landing
# writes the host form. Sound steps and prefills take 10-100 ms, the
# stalls this is for took 1.8-10 s (docs/32-tracing.md).
STALL_MS = 1000.0
# The launch records a stall record carries: the last this many.
LAUNCH_RING = 64
# What a step program lets the model write: the cache, and the expert
# choices a routed layer sows (nothing, for a model without one).
_MUTABLE = ["cache", "decisions"]
# On a block-diffusion model's record (ContinuousBatcher.
# _block_record): the name of the denoise pass that unmasked each
# position, and of a routed layer's choices in its block's s-th pass.
UNMASK_NAME = "unmask"
# A block-diffusion engine's counters (ContinuousBatcher._block_counts),
# in the order a reader lists them.
BLOCK_COUNTERS = ("block_denoise_passes", "block_commit_passes",
                  "block_positions_unmasked", "block_tokens_landed",
                  "block_commits_fused")


def pass_layer_name(layer: str, s: int) -> str:
    return f"{layer}.pass{s}"


@functools.partial(jax.jit, static_argnames=("model", "sampling"),
                   donate_argnames=("cache",))
def _decode_step(model, sampling, params, cache, tokens, positions,
                 active, key, draft=None, masked=None):
    """One token for every slot in one compiled call. MODULE-LEVEL
    with the model/sampling static so identical engines — fleet
    replicas sharing one param tree, or a test suite constructing
    many same-config engines — share ONE compilation instead of
    re-tracing per ContinuousBatcher instance.

    Like every step program below (the prefills, _speculative_step)
    this one CONSUMES the cache it is given (donate_argnames): the
    rows are written in place and the returned cache lives in the
    same buffers, so no step copies the pool or holds two of them.
    The cache passed in is deleted by the call — rebind it from the
    result in the same statement, and read nothing through an older
    reference. Parameters are shared between replicas and are not
    donated.

    A model with layers that choose experts (tfm.decision_layer_names)
    gives one result more, behind the four: the step's choices, int32
    [decision layers, B, k], each slot's at the position it fed.

    A model that carries its own drafter (TransformerConfig.
    mtp_modules) is handed ``draft`` [B] too, and the SAME program
    name then holds the verify-and-draft step (_verify_and_draft: one
    or two tokens a slot; its results behind the same first four).

    A model that generates by diffusion over blocks (TransformerConfig.
    block_diffusion) is handed ``masked`` [B, block] and tokens
    [B, 1, 2 * block], and the SAME program name then holds the block
    pass (_denoise_or_commit: no token or a whole block a slot; its
    results behind the same first four)."""
    if model.config.block_diffusion is not None:
        return _denoise_or_commit(model, params, cache, tokens, masked,
                                  positions, active)
    if model.config.mtp_modules:
        return _verify_and_draft(model, params, cache, tokens, draft,
                                 positions, active)
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens,
        positions=positions[:, None], live=active, mutable=_MUTABLE)
    next_tok = inf._sample(logits[:, 0].astype(jnp.float32),
                           key, sampling)
    # Inactive slots DO write one garbage row a step, and that is
    # fine: it lands where nothing reads it (row 0 of the slot's own
    # dense row; offset 0 of the scratch page, where a freed slot's
    # table points) and _admit's prefill rewrites the rows + cursor
    # before reuse — restoring the full K/V trees here would double
    # per-token HBM traffic for no observable effect. What needs
    # masking is the cheap bookkeeping: token, position, the cache
    # cursor (an idle slot leaves every step program with its cursor
    # at 0, so its garbage row stays at offset 0 of the scratch page)
    # and, for a paged pool, the length its attention call is handed:
    # ``live`` above makes it 0, and the paged decode kernel then
    # fetches no page and computes no tile for the slot.
    next_tok = jnp.where(active, next_tok, tokens[:, 0])
    positions = jnp.where(active, positions + 1, positions)
    cache = inf._park_idle_cursors(mutated["cache"], active)
    chosen = tfm.collect_decisions(mutated.get("decisions"),
                                   model.config)
    if chosen is None:
        return cache, next_tok[:, None], positions, next_tok
    return cache, next_tok[:, None], positions, next_tok, chosen[:, :, 0]


def _verify_and_draft(model, params, cache, tokens, draft, positions,
                      active):
    """_decode_step for a model that carries its own drafter (a
    multi-token-prediction module, transformer.MTPModule): ONE
    program, dispatched by the lookahead as every decode step is, that
    lands one or two tokens a slot. tokens [B, 1] is each slot's
    pending token y (landed, not yet cached) at ``positions`` [B],
    ``draft`` [B] the module's guess d of the token after it; every
    cache leaf (the stack's pool and rings, the module's pool) holds
    the committed positions before y's.

      verify  [y, d] through the stack at positions p, p+1: the grouped
              paged-decode kernel reads each live page once for both
              query positions; v0, v1 = the greedy tokens after y and
              after d
      accept  a = (v0 == d): the slot lands v0 alone, or v0 and v1
      module  over x_p = (Emb(v0), h_p) and x_{p+1} = (Emb(v1),
              h_{p+1}): its K/V rows, its routed choices, and the next
              draft, its greedy token at position p + a
      rewind  every cursor by 1 - a: a rejected draft's rows (pool,
              ring, the module's) lie beyond the cursor and are
              overwritten by the next step

    Greedy only, and lossless: a draft decides how many of the stack's
    own tokens land, never which. Nothing here reads the host: step
    k+1's y, d, positions and cursors are this program's results.
    -> (cache, tokens [B, 1], positions [B], v0 [B]: _decode_step's
    four, the token every slot lands (a caller that wraps the step and
    reads only those four finds them as _decode_step gives them:
    tests/benchmark/test_bench_rehearsal.py alters them); then v1 [B],
    accepted [B] int32 (0 for an inactive slot), draft [B][, the
    choices int32 [decision layers, B, 2, k] of the stack's routed
    layers and, last, the module's, at positions p and p+1])."""
    cfg = model.config
    block_in = jnp.concatenate([tokens, draft[:, None]], axis=1)
    pos_blk = positions[:, None] + jnp.arange(2, dtype=jnp.int32)[None]
    (logits, hidden), mutated = model.apply(
        {"params": params, "cache": cache}, block_in,
        positions=pos_blk, stack_hidden=True, live=active,
        mutable=_MUTABLE)
    verified = jnp.argmax(logits.astype(jnp.float32),
                          axis=-1).astype(jnp.int32)         # [B, 2]
    accepted = ((verified[:, 0] == draft) & active).astype(jnp.int32)
    chosen = tfm.collect_decisions(mutated.get("decisions"), cfg)
    mtp_logits, mtp_mutated = model.apply(
        {"params": params, "cache": mutated["cache"]}, verified,
        positions=pos_blk, mtp_hidden=hidden, live=active,
        mutable=_MUTABLE)
    drafted = jnp.argmax(mtp_logits.astype(jnp.float32),
                         axis=-1).astype(jnp.int32)          # [B, 2]
    mtp_chosen = tfm.collect_decisions(mtp_mutated.get("decisions"),
                                       cfg, mtp=True)
    cache = inf._park_idle_cursors(
        inf._rewind_cache(mtp_mutated["cache"], 1 - accepted), active)
    landed = accepted[:, None]
    new_tok = jnp.where(
        active[:, None],
        jnp.take_along_axis(verified, landed, axis=1), tokens)
    new_draft = jnp.where(
        active, jnp.take_along_axis(drafted, landed, axis=1)[:, 0],
        draft)
    positions = jnp.where(active, positions + 1 + accepted, positions)
    out = (cache, new_tok, positions, verified[:, 0], verified[:, 1],
           accepted, new_draft)
    picked = [part for part in (chosen, mtp_chosen) if part is not None]
    return out + (jnp.concatenate(picked),) if picked else out


def _unmasked_by(confidence, masked, rule):
    """The positions a denoise pass unmasks, bool [B, block], from the
    log-confidence of every position's own best token [B, block] and
    the mask before the pass: among the masked, the ``block // steps``
    most confident (ties: the lowest index; all of them where fewer
    are left), or under "low_confidence_dynamic" every one whose
    confidence is above the threshold where those are at least as
    many. An unmasked position is never chosen, so never masked
    again."""
    size = masked.shape[1]
    least = size // rule.steps
    score = jnp.where(masked, confidence, -jnp.inf)
    index = jnp.arange(size)
    # how many positions go before this one: a higher score, or the
    # same at a lower index
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (index[None, :] < index[:, None])[None])
    top = masked & (jnp.sum(ahead, axis=-1) < least)
    if rule.remask != "low_confidence_dynamic":
        return top
    above = masked & (confidence > jnp.log(jnp.float32(rule.threshold)))
    return jnp.where(
        jnp.sum(above, axis=-1, keepdims=True) >= least, above, top)


def _denoise_or_commit(model, params, cache, tokens, masked, positions,
                       active):
    """_decode_step for a model that generates by diffusion over blocks
    (transformer.BlockDiffusion): ONE program for all slots, dispatched
    by the lookahead as every decode step is, whose pass feeds TWO
    blocks a slot from the slot's cursor on, and commits a finished
    block in the pass that opens the next one. tokens [B, 1, 2 * block]
    holds in its first half each slot's open block at ``positions`` [B]
    (its first position, a whole number of blocks: the cursor of every
    cache leaf), ``masked`` [B, block] the positions of it that still
    read as the mask token (masked-ness is this boolean alone, never
    ``token == mask_id``: a prompt may hold that id; what lies under a
    mask is whatever the block held before and is read by nobody).
    Which of two passes a slot's is, its own data say:

      closing  no mask left: the first half fed is the finished block
               with its final tokens, the second the NEXT block, all
               masked. The first half's rows ARE the block's K/V and
               are kept (the cursor moves on by a block), the block's
               tokens land, and the second half is the next block's
               first denoise pass
      plain    masks left (a first block just seated among them): the
               first half is the open block, mask token where masked,
               and is denoised; the second half is dead filler, whose
               rows nobody reads: out of the attention's products
               (``live`` below), the head, the counters and the record

    Both halves go through the stack at positions p .. p + 2 * block
    - 1 against the cached blocks before them: the grouped paged kernel
    reads each live page once for all query positions, each of which
    sees the keys up to its own block's end (so the first half nothing
    of the second). The OPEN half alone (a closing slot's second, a
    plain slot's first) goes through the head: float32 logits, each
    position's best token and its confidence (that token's softmax
    probability, as its logarithm), and the pass's choice among the
    masked (_unmasked_by) takes its token. The cursor is rewound
    (inference._rewind_cache) past every row but a closing slot's first
    half, so that the rest lie beyond it and the next pass overwrites
    them.

    Greedy only. Nothing here reads the host: pass k+1's blocks, masks,
    positions and cursors are this program's results. -> (cache, tokens
    [B, 1, 2 * block], positions [B], the same tokens [B, 2 * block]:
    _decode_step's four (a caller that wraps the step and reads only
    those four finds them as _decode_step gives them), the open block
    after the pass and, behind it, the block as the pass found it,
    which a closing slot lands; then masked [B, block], flags int32
    [B, block + 1] (the positions of the open half this pass unmasked,
    then whether it closed a block; zeros for an inactive slot)[, the
    routed layers' choices int32 [decision layers, B, 2 * block, k]])."""
    cfg = model.config
    rule = cfg.block_diffusion
    size = rule.block
    block = tokens[:, 0, :size]                           # [B, block]
    closing = active & ~jnp.any(masked, axis=1)
    filler = jnp.full_like(block, rule.mask_id)
    span = jnp.arange(2 * size, dtype=jnp.int32)[None]
    logits, mutated = model.apply(
        {"params": params, "cache": cache},
        jnp.concatenate([jnp.where(masked, filler, block), filler],
                        axis=1),
        positions=positions[:, None] + span,
        # the query positions somebody reads
        live=jnp.where(active, jnp.where(closing, 2 * size, size), 0),
        head_rows=jnp.where(closing, size, 0)[:, None] + span[:, :size],
        mutable=_MUTABLE)
    logits = logits.astype(jnp.float32)
    best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    confidence = jnp.max(logits, axis=-1) - jax.nn.logsumexp(
        logits, axis=-1)
    masked = masked | closing[:, None]      # of the open half
    picked = _unmasked_by(confidence, masked, rule) & active[:, None]
    state = jnp.concatenate([jnp.where(picked, best, block), block],
                            axis=1)
    masked = masked & ~picked
    positions = jnp.where(closing, positions + size, positions)
    cache = inf._park_idle_cursors(inf._rewind_cache(
        mutated["cache"], jnp.where(closing, size, 2 * size)), active)
    flags = jnp.concatenate([picked, closing[:, None]],
                            axis=1).astype(jnp.int32)
    out = (cache, state[:, None], positions, state, masked, flags)
    chosen = tfm.collect_decisions(mutated.get("decisions"), cfg)
    return out if chosen is None else out + (chosen,)


@functools.partial(jax.jit, static_argnames=(
    "target_model", "draft_model", "gamma"),
    donate_argnames=("t_cache", "d_cache"))
def _speculative_step(target_model, draft_model, gamma, t_params,
                      d_params, t_cache, d_cache, tokens, positions,
                      active):
    """One ragged draft/verify round over the full slot batch.
    tokens [B, 1] is each slot's pending token y (sampled but not yet
    cached), positions [B] its absolute position — both caches hold
    every committed token EXCEPT y (the speculative_generate
    invariant, per slot).

    Draft: gamma+1 batched single-token steps propose d_1..d_gamma
    (the extra step only inserts d_gamma's K/V so the draft cache
    keeps pace on full acceptance). Verify: ONE batched target
    forward scores [y, d_1..d_gamma] through the multi-token
    cache-insert path (per-slot write indices + 2-D RoPE positions
    make the batch ragged-safe). Accept: each slot's longest
    validated prefix a_i, commit d_1..d_{a_i} plus the target token
    at a_i (correction or bonus), rewind both caches by gamma - a_i
    per slot — the paged target rewinds its per-slot length the same
    way. Inactive slots leave with their cursors parked at 0, like
    _decode_step's. Module-level jit (statics as above) so same-shape
    engines share the compilation."""
    d_embed = d_params["embed"]["embedding"]
    t_embed = t_params["embed"]["embedding"]

    def draft_step(carry, _):
        cache, tok, pos = carry
        hidden, mut = draft_model.apply(
            {"params": d_params, "cache": cache}, tok,
            return_hidden=True, positions=pos[:, None], live=active,
            mutable=["cache"])
        logits = jnp.dot(
            hidden[:, 0].astype(jnp.float32),
            d_embed.astype(jnp.float32).T)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (mut["cache"], nxt[:, None], pos + 1), nxt

    (d_cache, _, _), drafts = jax.lax.scan(
        draft_step, (d_cache, tokens, positions), None,
        length=gamma + 1)
    d_tok = jnp.moveaxis(drafts, 0, 1)[:, :gamma]        # [B, g]
    x_blk = jnp.concatenate([tokens, d_tok], axis=1)
    pos_blk = positions[:, None] + jnp.arange(
        gamma + 1, dtype=jnp.int32)[None, :]
    hidden, mut = target_model.apply(
        {"params": t_params, "cache": t_cache}, x_blk,
        return_hidden=True, positions=pos_blk, live=active,
        mutable=["cache"])
    t_cache = mut["cache"]
    logits = jnp.einsum(
        "bsd,vd->bsv", hidden.astype(jnp.float32),
        t_embed.astype(jnp.float32))
    t_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, g+1]
    match = (d_tok == t_tok[:, :gamma])
    a_slot = jnp.sum(jnp.cumprod(
        match.astype(jnp.int32), axis=1), axis=1)          # [B]
    a_slot = jnp.where(active, a_slot, 0)
    js = jnp.arange(gamma + 1, dtype=jnp.int32)
    d_pad = jnp.concatenate(
        [d_tok, jnp.zeros((d_tok.shape[0], 1), jnp.int32)], axis=1)
    block = jnp.where(js[None, :] < a_slot[:, None], d_pad,
                      t_tok)                               # [B, g+1]
    rewind = gamma - a_slot
    t_cache = inf._park_idle_cursors(
        inf._rewind_cache(t_cache, rewind), active)
    d_cache = inf._park_idle_cursors(
        inf._rewind_cache(d_cache, rewind), active)
    new_tok = jnp.take_along_axis(block, a_slot[:, None],
                                  axis=1)                  # [B, 1]
    new_tok = jnp.where(active[:, None], new_tok, tokens)
    new_pos = jnp.where(active, positions + a_slot + 1, positions)
    return t_cache, d_cache, new_tok, new_pos, block, a_slot


# The shortest segment window_segment gives: a window of 128 keys
# bounds nothing worth bounding, and a forward of 128 rows reads every
# weight to use a sixteenth of the MXU's rows.
SEGMENT_FLOOR = 2048


def window_segment(config) -> Optional[int]:
    """The engine's own segment length where it is handed no
    prefill_chunk: None (a bucket whole), but for a model with window
    layers whose inserts attend in blocks (prefill_blocks) the least
    power of two that holds its widest window (SEGMENT_FLOOR at
    least; SEGMENT_FLOOR for a model with latent attention, whose
    inserts expand what they attend), so that what a
    segment's forward holds in HBM (its rows through every projection
    and expert; the scores stay in VMEM) is bounded by the window
    whatever the bucket, and the power-of-two buckets are equal
    segments (one traced forward under a scan). Measured on a v5e at
    a window of 4,096 beside 12.0 GB of weights and cache (PERF.md,
    PR 40): segments of 2,048 serve 3 % fewer tokens a second; of
    8,192 or a 16,384 bucket whole 2-3 % more in a run that goes
    well, but with 1.9 / 2.6 GB of temporaries (1.2 at 4,096) every
    such run had a decode step land 1 to 2 s late, which none at
    4,096 had."""
    windows = [w for w in tfm.attention_windows(config) if w]
    if config.prefill_blocks and config.latent is not None:
        # a latent layer's insert expands keys and values for every
        # head from the rows it can see (H * (nope + rope + v) lanes a
        # row, against the cache's kv_rank + rope): what a segment
        # holds of that is bounded by the bucket, its queries by this
        return SEGMENT_FLOOR
    if not (config.prefill_blocks and windows):
        return None
    return max(1 << (max(windows) - 1).bit_length(), SEGMENT_FLOOR)


def _cached_len(config, prompt_len):
    """The positions a prefill leaves in the cache: the prompt's, but
    of a model that generates by diffusion over blocks the prompt's
    WHOLE blocks alone (the rest of the prompt opens the first
    generated block, whose rows the block's own passes write)."""
    rule = config.block_diffusion
    return prompt_len if rule is None else \
        prompt_len - prompt_len % rule.block


def _dense_prefill(model, prefill_chunk, params, prompt, prompt_len):
    """Batch-1 BATCHED prefill over the (bucket-padded) prompt
    [1, L]: the multi-token insert path of transformer._decode_attend
    writes all L cache rows and attends causally in MXU-batched
    passes — prefill wall-clock is one forward (or ceil(L/chunk)
    chunked forwards with prefill_chunk set, bounding the score
    tensor at O(chunk * max_decode_len)), not L sequential
    micro-steps. Compiles remain one per length bucket.

    prompt_len is DYNAMIC (a traced int32): rows written past
    prompt_len are garbage, but they are masked-on-read
    (key_pos <= idx) and each is overwritten by the decode step that
    first reaches its position, so only the length bookkeeping needs
    the true value. This is what makes L bucketable: one compile per
    BUCKET instead of one per distinct prompt length.

    The last-token logits come from the final hidden state at
    prompt_len-1 (return_hidden + a [d, vocab] matvec) so the full
    [L, vocab] fp32 logits tensor never materializes.

    What is NOT masked on read is a layer's running state
    (models/ssm.py, models/delta.py): the model is told how many of
    each segment's tokens are the prompt's own (valid_len), and leaves
    the state of position prompt_len-1. -> (cache, last logits, the
    routed layers' choices [decision layers, L, k] or None[, the first
    draft: _prefill_segments])."""
    return _prefill_segments(
        model, prefill_chunk, params, inf.init_cache(model, params, 1),
        prompt, 0, prompt_len)


def _prefill_segments(model, prefill_chunk, params, cache, tokens,
                      start, prompt_len):
    """``tokens`` [1, S] at positions start.. through the batch-1
    cache, in chunks; the logits at position prompt_len-1 (of a model
    that generates by diffusion over blocks, which reads no token off
    a prefill, that position's hidden row: the head stays out).
    Several
    chunks of one length are ONE traced forward under lax.scan (the
    cache its carry), so that a long bucket compiles, and weighs, what
    one chunk does and not chunks times that.

    A model that carries a multi-token-prediction module runs it
    behind the stack in every chunk, each position's NEXT token the
    prompt's own, so that the module's K/V of the whole prompt is in
    the cache; the prompt's last position, whose next token is the
    one this prefill samples (greedy), is run once more with it (one
    row, over the row the chunk wrote), and gives the FIRST DRAFT.
    -> (cache, last logits, choices or None) and, of a model with a
    module, the draft int32 [1] behind them."""
    cfg = model.config
    drafting = bool(cfg.mtp_modules)
    total = tokens.shape[1]
    chunk = min(prefill_chunk or total, total)
    following = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])],
        axis=1) if drafting else tokens
    # a cold prefill (start the Python int 0) reaches no row beyond
    # its bucket: what a latent layer expands of its cache
    reach = {"key_reach": total} if cfg.latent is not None and \
        isinstance(start, int) else {}

    def forward(cache, seg, nxt, off, positions):
        # Positions are GLOBAL offsets: RoPE for chunk c must match
        # the full-sequence pass exactly.
        at = dict(return_hidden=True, positions=start + positions,
                  valid_len=prompt_len - start - off, mutable=_MUTABLE,
                  **reach)
        h, mut = model.apply({"params": params, "cache": cache}, seg,
                             stack_hidden=drafting, **at)
        chosen = tfm.collect_decisions(mut.get("decisions"), cfg)
        if not drafting:
            return mut["cache"], (h, None), (chosen, None)
        h, stack_h = h
        _, mtp_mut = model.apply(
            {"params": params, "cache": mut["cache"]}, nxt,
            mtp_hidden=stack_h, **at)
        return mtp_mut["cache"], (h, stack_h), (
            chosen, tfm.collect_decisions(
                mtp_mut.get("decisions"), cfg, mtp=True))

    if total > chunk and total % chunk == 0:
        def step(cache, xs):
            seg, nxt, off = xs
            cache, hidden, chosen = forward(
                cache, seg, nxt, off,
                off + jnp.arange(chunk, dtype=jnp.int32))
            return cache, (hidden, chosen)

        count = total // chunk
        cache, (hidden, chosen) = jax.lax.scan(
            step, cache,
            (tokens.reshape(count, 1, chunk),
             following.reshape(count, 1, chunk),
             jnp.arange(count, dtype=jnp.int32) * chunk))
        hidden = tuple(None if h is None else h.reshape(
            1, total, h.shape[-1]) for h in hidden)
        # [chunks, layers, 1, chunk, k] -> [layers, S, k]
        chosen = tuple(None if c is None else jnp.moveaxis(
            c[:, :, 0], 0, 1).reshape(c.shape[1], total, c.shape[-1])
            for c in chosen)
    else:
        hiddens, picked = [], []
        for off in range(0, total, chunk):
            seg = tokens[:, off:off + chunk]
            cache, h, c = forward(
                cache, seg, following[:, off:off + chunk], off,
                jnp.arange(off, off + seg.shape[1], dtype=jnp.int32))
            hiddens.append(h)
            picked.append(c)
        hidden = tuple(
            None if part[0] is None else part[0] if len(part) == 1
            else jnp.concatenate(part, axis=1)
            for part in zip(*hiddens))
        chosen = picked
    normed = hidden[0][0]
    at = prompt_len - start - 1
    last = jnp.take(normed, at, axis=0)
    if cfg.block_diffusion is None:
        last = tfm.output_logits(cfg, params, last)          # [vocab]
    if isinstance(chosen, list):     # the loop's, a chunk an entry
        chosen = tuple(
            None if part[0] is None
            else jnp.concatenate(part, axis=2)[:, 0]
            for part in zip(*chosen))
    chosen, mtp_chosen = chosen
    if not drafting:
        return cache, last, chosen
    first = jnp.argmax(last).astype(jnp.int32)
    cache = {**cache, tfm.MTP_NAME: inf._map_cursors(
        lambda leaf: jnp.full_like(leaf, prompt_len - 1),
        cache[tfm.MTP_NAME])}
    out, mut = model.apply(
        {"params": params, "cache": cache}, first[None, None],
        return_hidden=True,
        positions=jnp.reshape(prompt_len - 1, (1, 1)),
        mtp_hidden=jnp.take(hidden[1], jnp.reshape(at, (1,)), axis=1),
        mutable=_MUTABLE, **reach)
    draft = jnp.argmax(tfm.output_logits(
        cfg, params, out[0, 0]))[None].astype(jnp.int32)
    if mtp_chosen is not None:
        fixed = tfm.collect_decisions(mut.get("decisions"), cfg,
                                      mtp=True)
        mtp_chosen = jax.lax.dynamic_update_slice(
            mtp_chosen, fixed[:, 0], (0, at, 0))
        chosen = mtp_chosen if chosen is None else jnp.concatenate(
            [chosen, mtp_chosen])
    return mut["cache"], last, chosen, draft


def _with_decisions(cache, last, chosen, draft=None):
    """A prefill program's results: behind the two every model gives,
    the first draft (a model with a multi-token-prediction module),
    then the routed layers' choices (a model that has such layers)."""
    return (cache, last) + tuple(
        part for part in (draft, chosen) if part is not None)


def _seat_state(big, small, slot):
    """A per-slot state leaf [B, ...] with the batch-1 prefill's in
    the slot's row: whatever the slot held before is gone."""
    return big.at[slot].set(small[0].astype(big.dtype))


def _seat_ring(big, small, slot, prompt_len, page: int) -> dict:
    """A window layer's ring leaves (transformer.
    Attention._decode_attend_ring: k_ring / v_ring [B * ring, page,
    Hkv*D], length [B]) with the batch-1 prefill's rows in the slot's
    ring: ring entry c takes the newest logical page p <= the
    prompt's last with p % ring == c (one gather of ``ring`` pages
    out of the dense rows, one update of the slot's stretch), and the
    slot's length is the prompt's. An entry no page of the prompt
    maps to yet takes page 0's rows: nothing reads it before the
    decode steps that reach it have written it."""
    ring = big["k_ring"].shape[0] // big["length"].shape[0]
    last = (prompt_len - 1) // page
    entry = jnp.arange(ring, dtype=jnp.int32)
    logical = jnp.maximum(last - jnp.mod(last - entry, ring), 0)

    def seated(pool, rows):
        pages = rows[0].reshape(-1, *pool.shape[1:])[logical]
        return jax.lax.dynamic_update_slice(
            pool, pages.astype(pool.dtype), (slot * ring, 0, 0))

    return {"k_ring": seated(big["k_ring"], small["k"]),
            "v_ring": seated(big["v_ring"], small["v"]),
            "length": big["length"].at[slot].set(prompt_len)}


def _seat_latent(big, rows, ids, slot, table_row, prompt_len) -> dict:
    """A latent layer's paged leaves (transformer.LatentAttention:
    kv_pages [P, page, lanes], block_table, length) with ``rows``
    [n * page, lanes] of a batch-1 prefill written as the pages
    ``ids`` [n] in ONE scatter (the dense rows ARE page rows), and the
    slot's table row and length set."""
    pool = big["kv_pages"]
    return {"kv_pages": pool.at[ids].set(rows.astype(pool.dtype).reshape(
                ids.shape[0], *pool.shape[1:])),
            "block_table": big["block_table"].at[slot].set(table_row),
            "length": big["length"].at[slot].set(prompt_len)}


def _pool_rows(rows, pool):
    """One page of dense-cache rows [page, H, D] as the paged pool
    stores them: its dtype, heads folded into the row ([page, H*D],
    transformer._decode_attend_paged)."""
    return rows.astype(pool.dtype).reshape(pool.shape[1:])


@functools.partial(jax.jit,
                   static_argnames=("model", "prefill_chunk"),
                   donate_argnames=("cache",))
def _prefill_dense(model, prefill_chunk, params, cache, slot, prompt,
                   prompt_len):
    """Fill ONE slot's cache region from a prompt [1, L] (batch-1
    forward, scattered into the slot row), returning the last-token
    logits for the first sample. The small cache's write index ran to
    L (the padded length); the slot's index is corrected to the true
    prompt_len. Module-level jit with a static model: same-config
    engines (fleet replicas, draft/target pairs) share one compile
    per length bucket."""
    small, last, chosen, *draft = _dense_prefill(
        model, prefill_chunk, params, prompt, prompt_len)

    def scatter(big, sm, path_key):
        if path_key == "index":
            return big.at[slot].set(_cached_len(model.config, prompt_len))
        return big.at[slot].set(sm[0])

    cache = jax.tree_util.tree_map_with_path(
        lambda kp, big, sm: scatter(
            big, sm, kp[-1].key if hasattr(kp[-1], "key")
            else str(kp[-1])),
        cache, small)
    return _with_decisions(cache, last, chosen, *draft)


@functools.partial(jax.jit,
                   static_argnames=("model", "prefill_chunk", "page"),
                   donate_argnames=("cache",))
def _prefill_paged(model, prefill_chunk, page, params, cache, slot,
                   prompt, table_row, prompt_len):
    """Paged variant: dense batch-1 prefill, rows scattered
    page-by-page into the slot's allocated pages; the slot's
    block-table row and length are set in every layer's cache copy.
    Full pages are written unconditionally: blocks past the
    allocation point at the scratch page (which absorbs
    padded-garbage writes), and partial-page garbage is
    masked-on-read via the true length. A layer's per-slot state
    (a leaf with a slot row and no pages) is overwritten whole, and a
    window layer's ring (_seat_ring) filled with the prompt's newest
    pages; neither reads ``table_row``."""
    small, last, chosen, *draft = _dense_prefill(
        model, prefill_chunk, params, prompt, prompt_len)
    # Bucket blocks, static (ceil: a bucket smaller than one page
    # still needs its first page written; the small cache has
    # max_decode_len >= n_blocks*page rows).
    n_blocks = -(-prompt.shape[1] // page)

    def scatter(big, sm):
        if isinstance(big, dict) and "k_ring" in big:
            return _seat_ring(big, sm, slot, prompt_len, page)
        if isinstance(big, dict) and "kv_pages" in big:
            return _seat_latent(big, sm["kv"][0, :n_blocks * page],
                                table_row[:n_blocks], slot, table_row,
                                prompt_len)
        if isinstance(big, dict) and "k_pages" in big:
            kp, vp = big["k_pages"], big["v_pages"]
            if model.config.prefill_blocks and "k_page_scales" not in big:
                # the dense rows ARE page rows ([T, Hkv*D]): every
                # block in one scatter (a 16,384 bucket is 256 blocks)
                def paged(rows, pool):
                    return rows[0, :n_blocks * page].astype(
                        pool.dtype).reshape(n_blocks, *pool.shape[1:])
                kp = kp.at[table_row[:n_blocks]].set(paged(sm["k"], kp))
                vp = vp.at[table_row[:n_blocks]].set(paged(sm["v"], vp))
            else:
                for b in range(n_blocks):
                    krows = sm["k"][0, b * page:(b + 1) * page]
                    vrows = sm["v"][0, b * page:(b + 1) * page]
                    kp = kp.at[table_row[b]].set(_pool_rows(krows, kp))
                    vp = vp.at[table_row[b]].set(_pool_rows(vrows, vp))
            out = {
                "k_pages": kp, "v_pages": vp,
                "block_table":
                    big["block_table"].at[slot].set(table_row),
                "length": big["length"].at[slot].set(
                    _cached_len(model.config, prompt_len)),
            }
            if "k_page_scales" in big:
                # int8 pool: the dense prefill cache is int8 too
                # (same kv_cache_dtype), so its rows and scales route
                # straight into the page pool.
                ksc = big["k_page_scales"]
                vsc = big["v_page_scales"]
                for b in range(n_blocks):
                    ksc = ksc.at[table_row[b]].set(
                        sm["k_scale"][0, b * page:(b + 1) * page])
                    vsc = vsc.at[table_row[b]].set(
                        sm["v_scale"][0, b * page:(b + 1) * page])
                out["k_page_scales"] = ksc
                out["v_page_scales"] = vsc
            return out
        if not isinstance(big, dict):
            return _seat_state(big, sm, slot)
        return {key: scatter(big[key], sm[key]) for key in big}

    return _with_decisions(scatter(cache, small), last, chosen, *draft)


@functools.partial(jax.jit,
                   static_argnames=("model", "prefill_chunk", "page"),
                   donate_argnames=("cache",))
def _prefill_paged_shared(model, prefill_chunk, page, params, cache,
                          slot, suffix, prefix_ids, table_row,
                          suffix_row, prefix_len, prompt_len):
    """Shared-prefix paged prefill: the request matched ``prefix_len``
    tokens (a whole number of pages, ids in ``prefix_ids``) in the
    engine's prefix index, so prefill SKIPS them — the forward runs
    only over ``suffix`` [1, S_bucket].

    Mechanics: (1) seed a batch-1 dense cache with the prefix K/V
    gathered straight out of the page pool
    (transformer.prefix_rows_from_pages) and set its write index to
    prefix_len; (2) run the suffix chunks through the model with
    GLOBAL positions prefix_len.. — the multi-token insert path
    attends causally over the seeded prefix exactly as a cold prefill
    would, and in fp32 produces the same bytes (the shared rows ARE
    the rows a cold prefill writes); (3) scatter only the suffix rows
    into the slot's freshly allocated pages (``suffix_row``, scratch-
    padded) and install the full block-table row + true length.

    prefix_len/prompt_len are dynamic (traced), so compiles key on the
    SUFFIX length bucket alone — a 1,000-token cached system prompt
    costs one gather (memory-bound) plus a short-bucket forward
    instead of a long-bucket prefill. prefix_ids is fixed-width
    (max_decode_len/page entries, scratch-padded): the gather reads a
    full cache width of pool rows per layer, which is the memcpy-class
    cost the skipped prefill FLOPs pay for."""
    small = inf.init_cache(model, params, 1)

    def seed(big, sm):
        if not isinstance(big, dict) or "k_ring" in big:
            # A per-slot state, and a window layer's ring, are of no
            # page: the engine sends a model that has one down the
            # whole-prompt path instead.
            raise NotImplementedError(
                "a shared-prefix prefill cannot seed a per-slot state "
                "or a window layer's ring")
        if isinstance(big, dict) and "kv_pages" in big:
            rows = big["kv_pages"][prefix_ids]
            rows = rows.reshape(-1, rows.shape[-1])
            return {"kv": sm["kv"].at[0, :rows.shape[0]].set(
                        rows.astype(sm["kv"].dtype)),
                    "index": jnp.full_like(sm["index"], prefix_len)}
        if isinstance(big, dict) and "k_pages" in big:
            rows = tfm.prefix_rows_from_pages(big, prefix_ids, page)
            nrows = rows["k"].shape[0]
            out = dict(sm)
            heads_depth = sm["k"].shape[2:]
            out["k"] = sm["k"].at[0, :nrows].set(
                rows["k"].astype(sm["k"].dtype).reshape(
                    nrows, *heads_depth))
            out["v"] = sm["v"].at[0, :nrows].set(
                rows["v"].astype(sm["v"].dtype).reshape(
                    nrows, *heads_depth))
            out["index"] = jnp.full_like(sm["index"], prefix_len)
            if "k_scale" in sm:
                out["k_scale"] = sm["k_scale"].at[0, :nrows].set(
                    rows["k_scale"])
                out["v_scale"] = sm["v_scale"].at[0, :nrows].set(
                    rows["v_scale"])
            return out
        return {key: seed(big[key], sm[key]) for key in sm}

    small, last, chosen, *draft = _prefill_segments(
        model, prefill_chunk, params, seed(cache, small), suffix,
        prefix_len, prompt_len)
    total = suffix.shape[1]
    # Suffix rows live at SMALL-cache rows prefix_len.. — dynamic
    # slices per page. Starts are page-multiples (prefix_len is a
    # whole number of pages), so the only slices that can clamp at
    # the buffer edge are bucket-padding blocks, and those target the
    # scratch page via suffix_row.
    n_blocks = -(-total // page)

    def scatter(big, sm):
        if isinstance(big, dict) and "kv_pages" in big:
            # one slice of the suffix's rows (a start that runs past
            # the cache is a padding block's, bound for the scratch
            # page)
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(sm["kv"][0], ((0, n_blocks * page), (0, 0))),
                prefix_len, n_blocks * page)
            return _seat_latent(big, rows, suffix_row[:n_blocks], slot,
                                table_row, prompt_len)
        if isinstance(big, dict) and "k_pages" in big:
            kp, vp = big["k_pages"], big["v_pages"]
            for b in range(n_blocks):
                start = prefix_len + b * page
                krows = jax.lax.dynamic_slice_in_dim(
                    sm["k"][0], start, page)
                vrows = jax.lax.dynamic_slice_in_dim(
                    sm["v"][0], start, page)
                kp = kp.at[suffix_row[b]].set(_pool_rows(krows, kp))
                vp = vp.at[suffix_row[b]].set(_pool_rows(vrows, vp))
            out = {
                "k_pages": kp, "v_pages": vp,
                "block_table":
                    big["block_table"].at[slot].set(table_row),
                "length": big["length"].at[slot].set(
                    _cached_len(model.config, prompt_len)),
            }
            if "k_page_scales" in big:
                ksc = big["k_page_scales"]
                vsc = big["v_page_scales"]
                for b in range(n_blocks):
                    start = prefix_len + b * page
                    ksc = ksc.at[suffix_row[b]].set(
                        jax.lax.dynamic_slice_in_dim(
                            sm["k_scale"][0], start, page))
                    vsc = vsc.at[suffix_row[b]].set(
                        jax.lax.dynamic_slice_in_dim(
                            sm["v_scale"][0], start, page))
                out["k_page_scales"] = ksc
                out["v_page_scales"] = vsc
            return out
        return {key: scatter(big[key], sm[key]) for key in big}

    return _with_decisions(scatter(cache, small), last, chosen, *draft)


@functools.partial(jax.jit, static_argnames=("copies",))
def _table_per_layer(table, copies):
    """The block table ``copies`` times over, each in a buffer of its
    own: the outputs of one program never share a buffer, so
    returning the argument once per layer is the copy."""
    return (table,) * copies


@functools.partial(jax.jit, static_argnames=("sampling",))
def _seat_first(sampling, last_logits, key, tokens, positions, slot,
                prompt_len):
    """A prefill's first token, sampled and seated ON THE DEVICE: the
    engine's key split once (the admission's key, as the decode
    program's caller splits the step's), the token drawn from the
    prefill's last logits [vocab] and written with the prompt's
    length into the slot's row of the decode step's inputs. -> (key,
    tokens [B, 1], positions [B], the token int32 [1]). The host
    reads the token when it likes (_land_first); the decode step
    behind this program needs nothing from the host. No bucket in
    its shapes, slot and length traced: ONE compilation an engine."""
    key, sample_key = jax.random.split(key)
    first = inf._sample(last_logits[None].astype(jnp.float32),
                        sample_key, sampling)
    return (key, tokens.at[slot, 0].set(first[0]),
            positions.at[slot].set(prompt_len), first)


@jax.jit
def _seat_draft(drafts, slot, draft):
    """A prefill's first draft [1] seated in the slot's row of the
    drafting decode step's input [B], behind _seat_first."""
    return drafts.at[slot].set(draft[0])


@jax.jit
def _seat_block(last_logits, tokens, masked, positions, slot, first,
                given, start):
    """_seat_first for a model that generates by diffusion over blocks:
    a prefill yields K/V and no token, and what is seated is the first
    generated BLOCK: ``first`` int32 [block] (the ``given`` tokens of
    the prompt past its whole blocks, then anything) into the slot's
    row of the block step's inputs (the open block's half), masked
    from ``given`` on, at the block's first position ``start``. ->
    (tokens [B, 1, 2 * block], masked
    [B, block], positions [B], int32 [1] that is ready when the
    prefill's ``last_logits`` (its last hidden row: _prefill_segments)
    are: what the host waits for in _land_first). Slot, count and
    position traced: ONE compilation an engine."""
    size = first.shape[0]
    return (tokens.at[slot, 0, :size].set(first),
            masked.at[slot].set(jnp.arange(size) >= given),
            positions.at[slot].set(start),
            jnp.isnan(last_logits[:1]).astype(jnp.int32))


@dataclasses.dataclass
class Request:
    request_id: str
    prompt: list[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    # Admission priority among QUEUED requests (higher admits first;
    # ties FIFO). Active slots are never preempted for priority —
    # this orders the wait line, like job.priority orders task
    # queues.
    priority: int = 0
    # Request-level SLO targets (None = best-effort): admission
    # orders same-priority entries by TTFT deadline, deferral guards
    # active slots' TPOT headroom against long prefill stalls, and —
    # when the engine is configured with a shed grace — overload
    # drops the deepest-deadline-violating entries instead of
    # serving them pointlessly late. Per-class defaults come from
    # config (config/settings.py ServingSloSettings); the front end
    # resolves slo_class -> targets before submit.
    ttft_target_ms: Optional[float] = None
    tpot_target_ms: Optional[float] = None
    slo_class: str = "standard"


@dataclasses.dataclass
class SpeculativeConfig:
    """Draft-model spec for ENGINE-INTEGRATED speculative decoding
    (the Leviathan draft/verify loop lifted out of
    models/inference.speculative_generate into the continuous batcher):
    each engine step drafts ``gamma`` tokens per active slot with the
    small draft model, verifies every slot's [y, d_1..d_gamma] block
    in ONE batched target forward, then commits/rewinds PER SLOT —
    slots advance 1..gamma+1 tokens per step, so all slot bookkeeping
    is variable-stride. Greedy-exact: outputs equal the
    non-speculative engine's for any draft quality (only throughput
    changes) — bit-exact in fp32 (the equivalence the tests pin);
    at reduced precision the usual multi-token caveat applies (the
    verify forward scores gamma+1 positions in one block, so under
    bf16 an argmax near-tie can resolve differently than single-step
    decode — same as models/inference.speculative_generate, see
    docs/15-serving.md). The draft always uses a dense KV cache
    (O(1) index rewind); the target may be dense or paged."""
    draft_config: tfm.TransformerConfig
    draft_params: object
    gamma: int = 4


@dataclasses.dataclass
class _Slot:
    """A slot's books on the host: who sits there, the tokens the
    host has read for it, and how many more the device owes it."""
    request: Optional[Request] = None
    generated: list[int] = dataclasses.field(default_factory=list)
    # Tokens of this request that the device was handed the programs
    # for and the host has not read yet, AT MOST: a prefill's first
    # token, 1 + drafts for a dispatched decode step (a step of a
    # model that drafts lands 1 to 1 + drafts tokens a slot, and the
    # host learns how many when it lands: _land settles the count).
    # What the host's books are behind the device by, the worst case:
    # page growth and occupancy reckon with it.
    in_flight: int = 0
    # The launches behind those tokens (a first token, a decode step):
    # each lands one token AT LEAST. Equal to in_flight for a model
    # that does not draft; 0 throughout for a model that generates by
    # diffusion over blocks, whose pass lands no token or a block.
    launches: int = 0
    # A block-diffusion model's open block: how many of its first
    # positions hold the prompt's tokens past its whole blocks (the
    # first generated block alone; they are given, not served).
    given: int = 0

    def decoding(self) -> bool:
        """Whether the next decode step advances this slot: seated
        and, the least the launches in flight will land counted (an
        unread first token too), still short of max_new_tokens. The
        host knows that finish before the token is computed; an eos
        finish, and a draft's acceptance that reaches max_new_tokens
        a step early, it learns from the landing (the step dispatched
        meanwhile computes overshoot: _land)."""
        return (self.request is not None and
                len(self.generated) + self.launches
                < self.request.max_new_tokens)

    def held_tokens(self) -> int:
        """Cached tokens the next decode step attends over, the row
        it writes included: one more than its write position (at
        most, where drafts are in flight)."""
        return (len(self.request.prompt) + len(self.generated) +
                self.in_flight)

    def ended(self) -> bool:
        """Whether the token read last is the request's last: the
        max_new_tokens-th, or its eos_id."""
        req = self.request
        return (len(self.generated) >= req.max_new_tokens or
                (req.eos_id is not None and
                 self.generated[-1] == req.eos_id))

    def served_of(self, tokens: list) -> list:
        """The leading tokens of a committed block that are served:
        up to the request's max_new_tokens-th, or its eos_id."""
        req = self.request
        tokens = tokens[:req.max_new_tokens - len(self.generated)]
        if req.eos_id is not None and req.eos_id in tokens:
            tokens = tokens[:tokens.index(req.eos_id) + 1]
        return tokens


@dataclasses.dataclass
class _InFlight:
    """A decode step the device was handed whose tokens the host has
    not read: the [B] token array (and the step's key, which dies
    with it), the (slot, request) pairs the step advances, taken at
    dispatch, when that was, how many results were unread then, and
    what the step's routed layers chose. It waits in
    ContinuousBatcher._unread, in dispatch order with the first
    tokens of prefills (_FirstToken), to be landed by _land."""
    tokens: object
    key: object
    seated: list[tuple[int, Request]]
    dispatched_at: float
    queued: int
    # The routed layers' choices of this step, int32 [decision layers,
    # B, k] on the device ([decision layers, B, 1 + drafts, k] of a
    # drafting step); None for a model without such layers.
    chosen: object = None
    # A drafting step (_verify_and_draft): drafts accepted a slot,
    # int32 [B] on the device, ``tokens`` then being 1 + drafts
    # arrays [B] of which a slot lands its first 1 + accepted.
    accepted: object = None
    # A block pass (_denoise_or_commit): int32 [B, block + 1] on the
    # device, the positions the pass unmasked and whether it closed a
    # block, ``tokens`` then being [B, 2 * block], whose second half a
    # closing slot lands.
    flags: object = None


@dataclasses.dataclass
class _FirstToken:
    """A prefill the device was handed whose first token the host has
    not read: the int32 [1] token array of the seat program
    (_seat_first), the slot it was seated in (whose request stays
    until the token has landed), the path and bucket its launch is
    recorded under, when it was dispatched, how many results were
    unread then, and what the prefill's routed layers chose."""
    token: object
    slot: int
    path: str
    bucket: int
    dispatched_at: float
    queued: int
    # The prefill's choices, int32 [decision layers, bucket, k] on the
    # device, of which the first ``prefilled`` positions are the
    # prompt's own, from position ``first_position`` on (past a
    # prefix taken from shared pages); None without routed layers.
    chosen: object = None
    prefilled: int = 0
    first_position: int = 0


@dataclasses.dataclass(slots=True)
class Launch:
    """One landed launch, as the engine saw it: what _land,
    _land_first and the speculative step hand to
    ContinuousBatcher._landed, the one record the counters, the
    admission estimates, a serve_step row's ``landed`` list and the
    stall ring are made from (docs/32-tracing.md).

    One launch is always in flight and the host blocks on the oldest
    unread result, so ``period_ms``, the time from the later of the
    launch's dispatch and the landing before it to its own landing,
    is how long the launch held the head of the device's queue AS THE
    HOST SAW IT: the device's time for it where the host was waiting
    (``ready`` false), and that plus the host's lateness where the
    device had finished before the host came to ask (``ready`` true,
    asked of the result once, just before the blocking read).
    ``behind_ms`` is how long the launch waited on the device's queue
    behind what was dispatched before it, ``queued`` how many results
    were unread when it was dispatched. Times are time.monotonic()."""
    kind: str                   # one of LAUNCH_KINDS
    dispatched_at: float
    landed_at: float
    period_ms: float
    behind_ms: float
    ready: bool
    queued: int
    rows: int = 0               # decode: the slots the step advanced
    path: str = ""              # prefill: cold/shared/recomputed/dense
    bucket: int = 0             # prefill: padded tokens
    # prefill: unpadded tokens; decode: the tokens the step landed
    # (rows + accepted)
    tokens: int = 0
    # decode: the drafts the step accepted over its rows (0 for a
    # model that does not draft)
    accepted: int = 0
    # decode: the rows whose pass closed a block (a model that
    # generates by diffusion over blocks; every row's denoised)
    commits: int = 0
    request_id: str = ""        # prefill
    # prefill: the road the bucket's program takes through its routed
    # experts (moe.experts_road: "dense" / "grouped"); "" for a model
    # without routed layers
    road: str = ""
    # prefill: the forwards the bucket's program runs (segments of at
    # most the engine's prefill_chunk)
    chunks: int = 0

    @classmethod
    def landing(cls, kind: str, dispatched_at: float, previous: float,
                ready: bool, queued: int, **what) -> "Launch":
        """The record of a launch that lands now, ``previous`` being
        when the launch before it landed."""
        now = time.monotonic()
        return cls(kind, dispatched_at, now,
                   (now - max(dispatched_at, previous)) * 1e3,
                   max(0.0, previous - dispatched_at) * 1e3,
                   ready, queued, **what)

    def entry(self) -> dict:
        """What a row's ``landed`` list and a stall record's ring
        hold of it."""
        out = {"kind": self.kind, "landed_at": self.landed_at,
               "period_ms": self.period_ms,
               "behind_ms": self.behind_ms, "ready": self.ready,
               "queued": self.queued}
        if self.kind == "decode":
            out.update(rows=self.rows, tokens=self.tokens,
                       accepted=self.accepted, commits=self.commits)
        else:
            out.update(path=self.path, bucket=self.bucket,
                       tokens=self.tokens,
                       request_id=self.request_id, road=self.road,
                       chunks=self.chunks)
        return out


def _ewma(estimate: Optional[float], sample: float) -> float:
    """The estimate moved three tenths of the way to the sample (the
    sample itself where there was none)."""
    return sample if estimate is None else \
        0.7 * estimate + 0.3 * sample


class _HostCounters(NamedTuple):
    """What a stall record takes deltas of, read at every landing (a
    microsecond each): the clock, the calling thread's CPU seconds,
    the process's user and system seconds, involuntary context
    switches and major faults, the collections of each gc generation
    and the compile counter's count."""
    at: float
    thread_cpu_s: float
    ru_utime_s: float
    ru_stime_s: float
    ru_nivcsw: int
    ru_majflt: int
    gc_collections: tuple
    compiles: int

    @classmethod
    def read(cls, compiles) -> "_HostCounters":
        usage = resource.getrusage(resource.RUSAGE_SELF)
        return cls(time.monotonic(), time.thread_time(),
                   usage.ru_utime, usage.ru_stime, usage.ru_nivcsw,
                   usage.ru_majflt,
                   tuple(gen["collections"] for gen in gc.get_stats()),
                   compiles.read()[0])


@dataclasses.dataclass
class _QueueEntry:
    """A queued request, plus the tokens it had already generated if
    it was preempted (overcommit mode): resumption re-prefills
    prompt + resumed in one pass and continues decoding — the greedy
    continuation is identical to the uninterrupted run."""
    request: Request
    resumed: list[int] = dataclasses.field(default_factory=list)
    # Monotonic submission stamp: the anchor for TTFT deadlines
    # (EDF ordering within a priority class, overload shedding).
    submitted_at: float = 0.0


class ContinuousBatcher:
    """Slot-based continuous batching engine.

    Usage:
        engine = ContinuousBatcher(config, params, num_slots=8,
                                   max_decode_len=2048)
        engine.submit(Request("r1", prompt_ids, max_new_tokens=128))
        while engine.pending():
            for request_id, tokens in engine.step():
                ...  # finished request
    """

    def __init__(self, config: tfm.TransformerConfig, params,
                 num_slots: int, max_decode_len: int,
                 sampling: inf.SamplingConfig = inf.SamplingConfig(),
                 seed: int = 0,
                 kv_page_size: Optional[int] = None,
                 kv_num_pages: Optional[int] = None,
                 overcommit: bool = False,
                 prefill_chunk: Optional[int] = None,
                 on_token: Optional[
                     Callable[[str, int, int], None]] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 prefix_cache: bool = True,
                 slo_shed_grace_ms: Optional[float] = None,
                 tpot_stall_factor: float = 4.0,
                 device=None):
        """device pins the engine to one jax device: the params are
        committed there (a copy, if they live elsewhere) and the KV
        cache is created there, so every step runs on that device —
        how N replicas in one process each get a chip of their own.
        None leaves everything on the default device.

        kv_page_size enables the PAGED KV cache (vLLM-style): K/V
        live in a shared kv_num_pages-page pool and slots hold block
        tables covering only their live tokens, so HBM is sized for
        aggregate active context instead of
        num_slots * max_decode_len. kv_num_pages defaults to the
        no-deadlock capacity (num_slots * ceil(max_len/page)). The
        pool's books are models/kv_pages.py, which has the two
        admission policies for a smaller pool: RESERVATION of each
        request's worst case (the default: decode can never exhaust
        the pool) and, with overcommit=True, the prompt's pages alone
        and PREEMPTION: a decode step that finds the pool dry
        re-queues the active slot with the fewest generated tokens,
        later resumed by re-prefilling prompt + generated tokens.

        prefix_cache (paged mode only) enables CROSS-REQUEST PREFIX
        REUSE: every full prompt page is indexed by a chained content
        hash at prefill, and a later request whose prompt starts with
        the same pages pins them instead of recomputing: its prefill
        runs only over the suffix (_prefill_paged_shared). Greedy
        outputs are unchanged (the shared rows are the bytes a cold
        prefill writes).

        slo_shed_grace_ms, when set, arms overload shedding: a queued
        request whose TTFT deadline has been missed by more than the
        grace is dropped (deepest violation first) instead of served
        pointlessly late — on_shed fires and the front end surfaces
        the drop as an error. tpot_stall_factor bounds admission's
        prefill-stall tolerance: a prefill predicted to stall active
        decodes longer than factor * (tightest active TPOT target) is
        deferred unless the candidate's own TTFT deadline is about to
        blow.

        prefill_chunk caps the CHUNKED PREFILL segment length: long
        prompts prefill in fixed-size multi-token inserts (each chunk
        attends causally over the cache, so the math is identical to
        one full-sequence pass) — the peak prefill score tensor
        shrinks from O(L * max_decode_len) to
        O(chunk * max_decode_len) (decode-path attention spans the
        full cache width). Compilation stays per length bucket (the
        chunk loop unrolls inside the bucket's jit). Use a power of
        two so chunks divide the power-of-two length buckets
        exactly."""
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = (window_segment(config)
                              if prefill_chunk is None else prefill_chunk)
        self.config = inf.decode_config(config, max_decode_len)
        self.paged = kv_page_size is not None
        self.overcommit = overcommit
        # Observer called as (request_id, token, index) the moment a
        # token is generated (index 0 = the prefill-sampled first
        # token) — the TTFT/TPOT measurement point for serving front
        # ends. Runs on the engine's stepping thread.
        self.on_token = on_token
        # The same observer, handed a landed step's tokens at once:
        # ONE call a step with [(request_id, token, index), ...]
        # (a prefill's first token: a batch of one). Where it is
        # set, on_token is not called (_emit).
        self.on_tokens: Optional[
            Callable[[list[tuple[str, int, int]]], None]] = None
        # Observer called as (request_id,) the moment a queued
        # request wins a slot, just before its prefill runs — the
        # queued->prefill boundary of the request's trace span chain
        # (models/server.py). Runs on the engine's stepping thread.
        self.on_admit: Optional[Callable[[str], None]] = None
        self.preemptions = 0
        self.speculative = speculative
        self.gamma = speculative.gamma if speculative else 0
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        if speculative is not None:
            if speculative.gamma < 1:
                raise ValueError(
                    f"speculative gamma must be >= 1, got "
                    f"{speculative.gamma}")
            if sampling.temperature > 0:
                raise ValueError(
                    "speculative serving is greedy-exact (draft "
                    "acceptance compares argmax chains); it requires "
                    "temperature == 0 sampling")
            if getattr(speculative.draft_config, "kv_page_size", None):
                raise ValueError(
                    "the draft model uses a dense KV cache (O(1) "
                    "index rewind); clear kv_page_size on the draft "
                    "config")
            if speculative.draft_config.vocab_size != \
                    config.vocab_size:
                raise ValueError(
                    "draft/target vocab_size must match (acceptance "
                    "compares token ids)")
        if overcommit and not self.paged:
            raise ValueError("overcommit requires the paged KV cache "
                             "(kv_page_size)")
        # A model whose layers keep a fixed-size state per slot
        # (models/ssm.py, models/delta.py) beside the K/V: the state
        # rides in the cache tree with a slot row and no cursor, is
        # overwritten when a request is seated and belongs to no page.
        self.stateful = tfm.has_slot_state(config)
        # The widest sliding window of the model's attention layers
        # (0: none has one). A window layer of the paged cache keeps
        # its newest keys in a ring a slot (transformer.ring_pages),
        # which no page of the pool names either.
        self.window = max(tfm.attention_windows(config), default=0)
        if speculative is not None and (
                self.stateful or self.window
                or not config.tie_embeddings):
            raise ValueError(
                "the two-model speculative path (a separate draft "
                "model with a dense cache, a serial step order) "
                "rewinds the cache by its cursor and scores drafts "
                "through the tied embedding: not for a model with a "
                "per-slot state, a window layer or an lm_head. A "
                "model that carries its own drafter "
                "(TransformerConfig.mtp_modules) needs no "
                "SpeculativeConfig and may have window layers and an "
                "lm_head")
        # A model that carries its own drafter (a multi-token-
        # prediction module: transformer.MTPModule): every decode step
        # verifies the module's draft and lands 1 to 1 + drafts tokens
        # a slot, on the lookahead's step order (_verify_and_draft).
        self.drafts = config.mtp_modules
        self.mtp_drafted = 0
        self.mtp_accepted = 0
        if self.drafts:
            if speculative is not None:
                raise ValueError(
                    "the model drafts by itself (mtp_modules): no "
                    "SpeculativeConfig beside it")
            if sampling.temperature > 0:
                raise ValueError(
                    "a model's own drafter is verified greedily "
                    "(lossless): it requires temperature == 0 "
                    "sampling")
            if self.stateful:
                raise NotImplementedError(
                    "a rejected draft is un-committed by the cursor: "
                    "not for a model with a per-slot state")
        # A model that generates by diffusion over blocks
        # (transformer.BlockDiffusion): every decode step is a pass
        # over each slot's open block and the one behind it and lands
        # no token or a whole block, on the lookahead's step order
        # (_denoise_or_commit). 0: one token a step.
        rule = config.block_diffusion
        self.block = rule.block if rule is not None else 0
        self.block_denoise_passes = 0
        # a pass that does nothing but commit: this engine has none (a
        # block is closed by the pass that opens the next), and the
        # counter stays for its readers
        self.block_commit_passes = 0
        self.block_positions_unmasked = 0
        self.block_tokens_landed = 0
        self.block_commits_fused = 0
        if self.block:
            if speculative is not None or self.drafts:
                raise ValueError(
                    "a block is denoised as one: no draft model and "
                    "no multi-token-prediction module beside "
                    "block_diffusion")
            if sampling.temperature > 0:
                raise NotImplementedError(
                    "block diffusion unmasks each position's best "
                    "token (greedy): it requires temperature == 0 "
                    "sampling")
            if self.stateful or self.window:
                raise NotImplementedError(
                    "a denoise pass is un-committed by the cursor and "
                    "sees its whole block: not for a model with a "
                    "per-slot state or a window layer")
            if not self.paged or kv_page_size % self.block or (
                    self.prefill_chunk or 0) % self.block:
                raise ValueError(
                    f"block diffusion needs the paged KV cache, pages "
                    f"and prefill chunks of whole blocks of "
                    f"{self.block} (kv_page_size {kv_page_size}, "
                    f"prefill_chunk {self.prefill_chunk}): a block "
                    f"then never straddles two pages or two chunks")
        # The layers that choose experts per position: their choices
        # leave every step program with the tokens and are kept per
        # request until take_decisions hands them over.
        self._decision_layers = tfm.decision_layer_names(config)
        self._decisions: dict[str, dict] = {}
        self._decisions_done: collections.OrderedDict = \
            collections.OrderedDict()
        self.expert_pairs_here = 0
        self.expert_pairs_chosen = 0
        self.experts_hit = 0
        # The page pool's books; None for a dense engine, and the
        # one thing paged dispatch asks.
        self.pages: Optional[kv_pages.PagePool] = None
        self.prefix_cache = bool(prefix_cache) and self.paged
        if self.paged:
            self.page_size = kv_page_size
            # what a step may write beyond the row it commits: the
            # drafts, or the two blocks of a block pass (the one
            # dispatched behind a request's last commit writes both
            # beyond it)
            margin = max(self.gamma, self.drafts, 2 * self.block)
            self.pages = kv_pages.PagePool(
                num_slots, kv_num_pages, kv_page_size, max_decode_len,
                spec_window=margin,
                overcommit=overcommit, prefix_cache=self.prefix_cache)
            # The pool's pages and, last, the scratch page.
            self.config = dataclasses.replace(
                self.config, kv_page_size=kv_page_size,
                kv_num_pages=self.pages.scratch_page + 1,
                spec_window=margin)
        # SLO scheduling state: live EWMA estimates of prefill cost
        # per bucket token and of the decode step feed admission's
        # stall prediction; sheds/deferrals are the overload
        # counters per-class attainment reporting builds on.
        self.slo_shed_grace_ms = slo_shed_grace_ms
        self.tpot_stall_factor = tpot_stall_factor
        self.slo_sheds = 0
        self.sheds_by_class: dict[str, int] = {}
        self.slo_deferrals = 0
        self.on_shed: Optional[Callable[[str, str], None]] = None
        # Drain mode (serving fault tolerance): once set, _admit
        # refuses to seat new work — active decodes run to completion
        # while the queue is handed back to the caller for failover.
        self.draining = False
        self._prefill_ms_per_token: Optional[float] = None
        self._step_ms: Optional[float] = None
        # the (path, bucket) whose first landing, a compile, is past
        self._prefill_shapes: set = set()
        # Step tracing: per-phase seconds and step counts are always
        # on (a few float adds a step); a serve_step row is written
        # only while the process-local span recorder is switched on
        # ($SHIPYARD_TRACE_FILE), head-sampled by traced_steps, which
        # a front end resets when it takes the engine over.
        self._phases = trace_spans.PhaseTimer(PHASE_PREFIX, STEP_PHASES)
        self._compiles = trace_spans.compile_counter()
        self.steps_total = 0
        self.step_seconds_total = 0.0
        self.traced_steps = 0
        # The lookahead (_step): what the device was handed and the
        # host has not read, in dispatch order (a decode step in
        # flight, prefills' first tokens), requests a landing
        # finished that no step() has returned yet, when the last
        # result landed, and the counters (step_stats).
        self._unread: collections.deque = collections.deque()
        self._finished: list[tuple[str, list[int]]] = []
        # The device's timeline from the engine's own landings
        # (_landed), always on: when the last launch landed, the
        # cumulative counters by kind, the last LAUNCH_RING records
        # and the host's counters as of the last landing (for a
        # stall record), the launches landed and the dry seconds
        # ended since the last step() that did anything (for its
        # row), and since when the device has had nothing to run
        # (None while it has: _settle, _dispatching).
        self._landed_at = time.monotonic()
        self.launches = dict.fromkeys(LAUNCH_KINDS, 0)
        self.launch_seconds = dict.fromkeys(LAUNCH_KINDS, 0.0)
        self.landings_ready = dict.fromkeys(LAUNCH_KINDS, 0)
        self.prefill_bucket_tokens = 0
        self.prefill_tokens = 0
        self.prefills_grouped = 0
        self.no_work_seconds = 0.0
        self.stalls = 0
        self._ring: collections.deque = collections.deque(
            maxlen=LAUNCH_RING)
        self._host_then = _HostCounters.read(self._compiles)
        self._landed_call: list[Launch] = []
        self._no_work_call = 0.0
        self._dry_since: Optional[float] = self._landed_at
        self.decode_steps = 0
        self.steps_overlapped = 0
        self.prefills = 0
        self.prefills_overlapped = 0
        self.settles = dict.fromkeys(SETTLE_CAUSES, 0)
        self.overshoot_tokens = 0
        # Row ids count up from a random start: a uuid4 a step costs
        # a getrandom() call, a third of a millisecond where that is
        # slow.
        self._row_ids = itertools.count(random.getrandbits(32))
        self._admitted: list[dict] = []
        self.model = tfm.TransformerLM(self.config)
        self.device = device
        on_device = (jax.default_device(device) if device is not None
                     else contextlib.nullcontext())
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.num_slots = num_slots
        self.max_decode_len = max_decode_len
        self.sampling = sampling
        # Created on the engine's device, then committed to it (a
        # no-op move): committed state keeps the eager bookkeeping
        # ops between steps (key splits, .at[].set) on that device
        # too.
        with on_device:
            (self.cache, self._tokens, self._positions, self._active,
             self._key, self._draft, self._masked) = self._put((
                 inf.empty_cache(self.model, num_slots),
                 # each slot's pending token, or its open block and
                 # the one it landed last
                 jnp.zeros((num_slots, 1) + (
                     (2 * self.block,) if self.block else ()),
                     jnp.int32),
                 jnp.zeros((num_slots,), jnp.int32),
                 jnp.zeros((num_slots,), jnp.bool_),
                 jax.random.PRNGKey(seed),
                 # each slot's draft of the token after its pending one
                 jnp.zeros((num_slots,), jnp.int32),
                 # the positions of each slot's open block still masked
                 jnp.ones((num_slots, self.block), jnp.bool_)))
        self._slot_state_bytes = inf.slot_state_bytes(self.cache)
        if self.pages is not None:
            self.pages.page_bytes = inf.pool_page_bytes(self.cache)
        # _active as the host last pushed it (_push_active).
        self._active_host = np.zeros((num_slots,), np.bool_)
        if self.pages is not None:
            # Fresh caches default block tables to zeros (a REAL
            # page); point every slot at the scratch page before any
            # step runs.
            self._push_tables()
        self._slots = [_Slot() for _ in range(num_slots)]
        self._queue: list[_QueueEntry] = []

        self._decode_step = functools.partial(
            _decode_step, self.model, self.sampling)
        self._seat_first = functools.partial(_seat_first,
                                             self.sampling)

        # Prefill always runs on a DENSE batch-1 decode model sharing
        # the params; paged mode then scatters its rows into the
        # slot's allocated pages. The prefill fns are module-level
        # static-model jits (the speculative path binds the same
        # machinery to the DRAFT model, and same-config engines share
        # compiles).
        dense_model = tfm.TransformerLM(
            inf.decode_config(config, max_decode_len))
        page = getattr(self, "page_size", 0)
        self._prefill = functools.partial(
            _prefill_dense, dense_model, self.prefill_chunk)
        self._prefill_paged = functools.partial(
            _prefill_paged, dense_model, self.prefill_chunk, page)
        self._prefill_shared = functools.partial(
            _prefill_paged_shared, dense_model, self.prefill_chunk,
            page)

        if speculative is not None:
            # Draft engine state: a dense cache with gamma+1 extra
            # rows so a draft block starting at max_decode_len-2 never
            # wraps (the target cache needs no extra rows — its
            # out-of-bounds tail scatters drop, and every key a
            # COMMITTED query reads is in bounds by construction).
            draft_model = tfm.TransformerLM(inf.decode_config(
                speculative.draft_config,
                max_decode_len + self.gamma + 1))
            self._draft_params = (
                speculative.draft_params if device is None else
                jax.device_put(speculative.draft_params, device))
            with on_device:
                self._draft_cache = self._put(inf.empty_cache(
                    draft_model, num_slots))
            self._draft_prefill = functools.partial(
                _prefill_dense, draft_model, self.prefill_chunk)
            self._spec_step = functools.partial(
                _speculative_step, self.model, draft_model,
                self.gamma)

    @property
    def _recomputes_matched(self) -> bool:
        """Whether a request that matched pages of the prefix index
        still prefills its whole prompt ("recomputed"): a per-slot
        state and a window layer's ring are of no page, so a matched
        prefix never stands in for them."""
        return self.stateful or bool(self.window)

    # tests/benchmark/test_bench_reference.py reads these two off the
    # engine to build a table row for _prefill_paged.
    max_blocks = property(lambda self: self.pages.max_blocks)
    _scratch_page = property(lambda self: self.pages.scratch_page)

    @property
    def _in_flight(self) -> Optional[_InFlight]:
        """The decode step the device was handed whose tokens the
        host has not read (the newest, while _step holds two)."""
        return next((result for result in reversed(self._unread)
                     if isinstance(result, _InFlight)), None)

    # ------------------------------ public -----------------------------

    def warmup_buckets(self) -> list[int]:
        """Every prefill compile bucket this engine can serve,
        DERIVED from _bucket_length (the one source of the bucket
        rule): walk each bucket's successor until the cap."""
        buckets = [self._bucket_length(1)]
        while buckets[-1] < self.max_decode_len:
            buckets.append(self._bucket_length(buckets[-1] + 1))
        return buckets

    def warmup(self, prompt_len: Optional[int] = None,
               max_new_tokens: int = 2) -> list[int]:
        """Drive throwaway requests through prefill + decode so the
        jit compiles happen before real traffic, recorded as an
        engine warm-up goodput phase (compile-leg badput; see
        goodput/accounting.py) with the persistent compile cache's
        hit/saved detail when one is enabled. By default EVERY prefill
        length bucket up to max_decode_len is warmed — one request per
        bucket, drained sequentially — so the first long-prompt
        request never pays a mid-traffic compile; the decode step and,
        when a draft model is configured, the speculative draft/verify
        paths compile on the first request. ``prompt_len`` pins a
        single warm-up request instead. Serving front ends call this
        before accepting load so warm-up never pollutes TTFT. Returns
        the buckets warmed."""
        from batch_shipyard_tpu.compilecache import (
            manager as cc_manager)
        from batch_shipyard_tpu.goodput import events as goodput_events
        if prompt_len is not None:
            lengths = [prompt_len]
        else:
            lengths = [min(bucket,
                           self.max_decode_len - max_new_tokens)
                       for bucket in self.warmup_buckets()]
            if self.pages is not None:
                # A deliberately tight page pool (overcommit sizing)
                # cannot admit the longest buckets' worst case: skip
                # them rather than fail startup — they compile on
                # first real (admittable) use, as before.
                lengths = [
                    length for length in lengths
                    if self.pages.pages_for(length + max_new_tokens)
                    <= self.pages.num_pages]
        warmed: list[int] = []

        def drain(length: int) -> None:
            self.submit(Request(
                request_id=f"__warmup__{uuid.uuid4().hex[:8]}",
                prompt=[(i % 7) + 1 for i in range(length)],
                max_new_tokens=max_new_tokens))
            while self.pending():
                self.step()

        with goodput_events.phase(goodput_events.PROGRAM_WARMUP,
                                  what="serving_engine",
                                  buckets=len(lengths)) as attrs, \
                cc_manager.tracked(attrs, "serving_warmup"):
            for length in lengths:
                if self.prefix_cache:
                    # The warm-up prompts share prefixes, so with the
                    # index live a long bucket would match the
                    # previous bucket's published pages and compile
                    # the SHARED path instead of its cold prefill —
                    # and the first novel long prompt in real traffic
                    # would then pay that compile mid-measurement.
                    # Match against an empty index so every bucket
                    # compiles cold.
                    self.prefix_cache_clear()
                drain(length)
                warmed.append(self._bucket_length(length))
            if self.prefix_cache and len(lengths) > 1:
                # Second pass compiles the shared-prefill suffix
                # buckets: starting from an empty index, each chained
                # prompt matches the full pages the previous bucket's
                # request published, leaving only the suffix to
                # prefill.
                self.prefix_cache_clear()
                for length in lengths:
                    drain(length)
        if self.prefix_cache:
            # Real traffic should start against an empty index, and
            # the stats should describe real traffic only — not the
            # warm-up's synthetic lookups and publishes.
            self.prefix_cache_clear()
            self.pages.reset_stats()
        return warmed

    def precompile(self) -> int:
        """AOT warm start from shapes — no throwaway requests: lower +
        compile the decode step (or the speculative draft/verify step),
        every prefill bucket and the seat program behind a prefill
        against ShapeDtypeStruct abstract inputs placed as the real
        calls' arguments are (aot.abstractify). The executables are discarded; the value is the
        PERSISTENT compilation cache (compilecache/manager.py) they
        populate, which turns the first real request's jit compiles
        into fast deserializes — so enable the cache first, or this
        compiles twice for nothing. Returns the number of functions
        compiled."""
        import jax as jax_mod

        from batch_shipyard_tpu.compilecache import aot
        from batch_shipyard_tpu.compilecache import (
            manager as cc_manager)
        from batch_shipyard_tpu.goodput import events as goodput_events
        count = 0
        with goodput_events.phase(goodput_events.PROGRAM_WARMUP,
                                  what="serving_aot") as attrs, \
                cc_manager.tracked(attrs, "serving_precompile"):
            params_abs = aot.abstractify(self.params)
            cache_abs = aot.abstractify(self.cache)
            tokens_abs, pos_abs, active_abs, key_abs = aot.abstractify(
                (self._tokens, self._positions, self._active,
                 self._key))

            def put_abs(shape):
                # what _put gives: int32, on the engine's device if
                # it is pinned to one
                return jax_mod.ShapeDtypeStruct(
                    shape, jnp.int32, sharding=active_abs.sharding)
            if self.speculative is not None:
                _speculative_step.lower(
                    self.model, self._spec_step.args[1], self.gamma,
                    params_abs, aot.abstractify(self._draft_params),
                    cache_abs, aot.abstractify(self._draft_cache),
                    tokens_abs, pos_abs, active_abs).compile()
            else:
                _decode_step.lower(
                    self.model, self.sampling, params_abs, cache_abs,
                    tokens_abs, pos_abs, active_abs, key_abs,
                    *([aot.abstractify(self._draft)]
                      if self.drafts else []),
                    **({"masked": aot.abstractify(self._masked)}
                       if self.block else {})).compile()
            count += 1
            dense_model = self._prefill.args[0]
            for bucket in self.warmup_buckets():
                prompt_abs = put_abs((1, bucket))
                if self.pages is not None:
                    row_abs = put_abs((self.max_blocks,))
                    lowered = _prefill_paged.lower(
                        dense_model, self.prefill_chunk,
                        self.page_size, params_abs, cache_abs, 0,
                        prompt_abs, row_abs, bucket)
                else:
                    lowered = _prefill_dense.lower(
                        dense_model, self.prefill_chunk, params_abs,
                        cache_abs, 0, prompt_abs, bucket)
                lowered.compile()
                count += 1
                if self.speculative is not None:
                    # _admit prefills the DRAFT cache too (the
                    # spec-step invariant) — a distinct compile per
                    # bucket that would otherwise hit mid-traffic.
                    _prefill_dense.lower(
                        self._draft_prefill.args[0],
                        self.prefill_chunk,
                        aot.abstractify(self._draft_params),
                        aot.abstractify(self._draft_cache), 0,
                        prompt_abs, bucket).compile()
                    count += 1
            # The seat program behind every prefill (_seat_first): it
            # takes a prefill's last logits, whatever the bucket.
            logits = lowered.out_info[1]
            logits_abs = jax_mod.ShapeDtypeStruct(
                logits.shape, logits.dtype, sharding=active_abs.sharding)
            if self.block:
                _seat_block.lower(
                    logits_abs, tokens_abs,
                    aot.abstractify(self._masked), pos_abs, 0,
                    put_abs((self.block,)), 0, 0).compile()
            else:
                _seat_first.lower(
                    self.sampling, logits_abs, key_abs, tokens_abs,
                    pos_abs, 0, 0).compile()
            count += 1
        return count

    def submit(self, request: Request,
               resumed: Optional[list[int]] = None) -> None:
        """Enqueue a request. ``resumed`` carries tokens already
        emitted by a prior (killed or drained) replica: the entry
        re-prefills prompt+resumed in one pass and decoding continues
        from there, so a greedy stream is byte-identical to an
        uninterrupted run. Refused while draining — the caller must
        fail over to a sibling."""
        if self.draining:
            raise ValueError(
                f"{request.request_id}: engine is draining")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"{request.request_id}: max_new_tokens must be >= 1")
        if not request.prompt:
            raise ValueError(
                f"{request.request_id}: prompt must be non-empty")
        resumed = [int(t) for t in (resumed or [])]
        if len(resumed) >= request.max_new_tokens:
            raise ValueError(
                f"{request.request_id}: resumed tokens "
                f"{len(resumed)} >= max_new_tokens "
                f"{request.max_new_tokens} — nothing left to decode")
        if self.pages is not None:
            worst = self.pages.pages_for(
                len(request.prompt) + request.max_new_tokens)
            if worst > self.pages.num_pages:
                raise ValueError(
                    f"{request.request_id}: worst-case page need "
                    f"{worst} exceeds the pool ({self.pages.num_pages}"
                    f" pages) — it could never admit")
        if len(request.prompt) + request.max_new_tokens > \
                self.max_decode_len:
            raise ValueError(
                f"{request.request_id}: prompt+generation "
                f"{len(request.prompt)}+{request.max_new_tokens} "
                f"exceeds max_decode_len {self.max_decode_len}")
        self._enqueue(_QueueEntry(request, resumed=resumed,
                                  submitted_at=time.monotonic()))

    def pending(self) -> int:
        """Requests queued or seated, and finished ones the next
        step() has yet to return (a cancel can land the step in
        flight between two steps)."""
        return len(self._queue) + len(self._finished) + sum(
            1 for s in self._slots if s.request is not None)

    def drain(self) -> list[str]:
        """Flip the engine into drain mode: what the device owes is
        landed (_settle), _admit stops seating new work, queued
        entries (which hold no pages) are evicted and
        their ids returned so the front end can 503 their waiters for
        router failover, and active decodes keep stepping until they
        finish (or the front end's grace deadline cancels them).
        Idempotent; must be called from the engine's stepping
        thread — it mutates the queue like _admit does."""
        self._settle("drain")
        self.draining = True
        evicted = [e.request.request_id for e in self._queue]
        self._queue.clear()
        return evicted

    def cache_lost(self) -> bool:
        """Whether a step program consumed the cache and gave none
        back: the step programs donate the cache they are given, so
        one that raises after its buffers were handed over leaves
        ``self.cache`` holding deleted arrays, and no further step
        can run. For the caller of a step() that raised; nothing on
        the sound path asks."""
        caches = [self.cache]
        if self.speculative is not None:
            caches.append(self._draft_cache)
        return any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(caches))

    def active_request_ids(self) -> list[str]:
        """Ids currently decoding in a slot (in-flight work a drain
        lets run to completion)."""
        return [s.request.request_id for s in self._slots
                if s.request is not None]

    def cancel(self, request_id: str) -> bool:
        """Abort a queued or actively-decoding request (the vLLM-class
        abort operation). Queued entries are removed; an active slot
        is freed immediately (its pages return to the pool), after
        the step in flight and any first token still unread have
        been read back: every token computed for the request before
        the cancel is delivered, and one that ends it finishes it
        (False then, and the next step() returns it). Must be called from the engine's stepping thread — it
        mutates slot state like step() does. Returns False when the
        id is unknown (already finished)."""
        for k, entry in enumerate(self._queue):
            if entry.request.request_id == request_id:
                del self._queue[k]
                return True
        seat = next((i for i, slot in enumerate(self._slots)
                     if slot.request is not None and
                     slot.request.request_id == request_id), None)
        if seat is None:
            return False
        # What is unread (the step in flight, a first token) may
        # hold the request's next token, or its last, which frees the
        # slot by itself.
        self._settle("cancel")
        if self._slots[seat].request is None:
            return False
        self._free_slot(seat)
        return True

    # Step-row head-sampling, the request spans' rule
    # (server.ServingFrontEnd._SPAN_HEAD): the first _STEP_HEAD steps
    # a front end drives are recorded in full (a 51 s window with its
    # lead-in and drain, at 20 steps a second, four times over), then
    # 1-in-_STEP_SAMPLE_EVERY. The cumulative counters see every step.
    _STEP_HEAD = 4096
    _STEP_SAMPLE_EVERY = 16

    def step(self) -> list[tuple[str, list[int]]]:
        """Admit queued requests into free slots, decode for every
        active slot — one token per step, or a gamma-token
        draft/verify block per slot when speculative decoding is
        configured — and return the requests that finished. Without
        a draft model ONE DECODE STEP STAYS IN FLIGHT (_step): this
        call dispatches the next one and only then reads back and
        emits the tokens of the one before, so a request's last
        token, and with it the request, comes back from the call
        AFTER the one that dispatched it. Every part of the step
        runs inside one of STEP_PHASES; with the span recorder on, a
        step that did anything also writes a serve_step row."""
        phases = self._phases
        phases.reset()
        self._admitted.clear()
        traced = trace_spans.local_spans_path() is not None
        wall0, t0 = time.time(), time.monotonic()
        # for a stall record of the host form (_stalled)
        landed0, stalls0 = len(self._landed_call), self.stalls
        host_then = self._host_then
        before = compiles0 = lookahead0 = None
        if traced and (self.traced_steps < self._STEP_HEAD or
                       (self.traced_steps + 1)
                       % self._STEP_SAMPLE_EVERY == 0):
            before = self.occupancy()
            compiles0 = self._compiles.read()
            lookahead0 = {**self._lookahead_counts(),
                          **self._expert_counts(),
                          **self._mtp_counts(),
                          **self._block_counts()}
        self._step()
        seconds = time.monotonic() - t0
        if seconds * 1e3 > STALL_MS / 4 and self.stalls == stalls0:
            self._host_stalled(seconds * 1e3, landed0, host_then)
        finished, self._finished = self._finished, []
        if not self._admitted and \
                phases.step.keys().isdisjoint(("dispatch", "readback")):
            return finished     # nothing to seat, decode or read back
        self.steps_total += 1
        self.step_seconds_total += seconds
        if traced:
            self.traced_steps += 1
        if before is not None:
            self._record_step_row(wall0, t0, seconds, before,
                                  compiles0, lookahead0, len(finished))
        # What a settle outside step() lands (cancel, drain) stays
        # for the next row.
        self._landed_call.clear()
        self._no_work_call = 0.0
        return finished

    def _lookahead_counts(self) -> dict:
        """The lookahead's cumulative counters: decode steps
        dispatched, how many of them while their predecessor was
        still unread, prefill programs dispatched, how many of them
        behind a decode step in flight or behind another prefill of
        the same call, the settles by cause (SETTLE_CAUSES), and the
        tokens computed for a request that had already ended."""
        return {"decode_steps": self.decode_steps,
                "steps_overlapped": self.steps_overlapped,
                "prefills": self.prefills,
                "prefills_overlapped": self.prefills_overlapped,
                "settles": dict(self.settles),
                "overshoot_tokens": self.overshoot_tokens}

    def _record_step_row(self, wall0: float, t0: float,
                         seconds: float, before: dict,
                         compiles0: tuple, lookahead0: dict,
                         finished: int) -> None:
        attrs = {"mono_start": t0}
        for name in STEP_PHASES:
            attrs[f"{name}_ms"] = self._phases.step.get(name,
                                                        0.0) * 1e3
        attrs["prefills"] = len(self._admitted)
        attrs["prefills_overlapped"] = (
            self.prefills_overlapped
            - lookahead0["prefills_overlapped"])
        attrs["admitted"] = list(self._admitted)
        # The launches this call landed (and a cancel or a drain
        # since the last row), oldest first, and the dry spell, if
        # any, that its first dispatch ended.
        attrs["landed"] = [launch.entry()
                           for launch in self._landed_call]
        attrs["no_work_seconds"] = self._no_work_call
        attrs["finished"] = finished
        # What this call added to the lookahead's counters: whether
        # its decode step overlapped the one before, why not (the
        # settles' causes), the overshoot it discarded.
        now = self._lookahead_counts()
        attrs["overlapped"] = (now["steps_overlapped"]
                               - lookahead0["steps_overlapped"])
        attrs["settles"] = [
            cause for cause in SETTLE_CAUSES
            for _ in range(now["settles"][cause]
                           - lookahead0["settles"][cause])]
        attrs["overshoot_tokens"] = (now["overshoot_tokens"]
                                     - lookahead0["overshoot_tokens"])
        if self._decision_layers:
            # of the decode step this call landed (none: all 0)
            for name, value in self._expert_counts().items():
                attrs[name] = value - lookahead0[name]
        if self.drafts:
            # of the decode step this call landed
            for name, value in self._mtp_counts().items():
                attrs[name] = value - lookahead0[name]
        if self.block:
            # of the block pass this call landed
            for name, value in self._block_counts().items():
                attrs[name] = value - lookahead0[name]
        attrs.update(before)
        count, compile_s = self._compiles.read()
        if count > compiles0[0]:
            attrs["compiles"] = count - compiles0[0]
            attrs["compile_ms"] = (compile_s - compiles0[1]) * 1e3
        trace_spans.record(
            trace_spans.SPAN_SERVE_STEP, wall0, wall0 + seconds,
            span_id=f"{next(self._row_ids) & 0xffffffff:08x}", **attrs)

    def _step(self) -> None:
        """One call's work; what finished goes to self._finished.

        Without a draft model the device is never waited for before
        it has its next work. Step k's inputs (cache, tokens,
        positions) are device arrays that step k-1, or a prefill and
        its seat program behind it, returned, so the device needs
        nothing from the host to go from one to the next. This call
        dispatches the queue's prefills BEHIND the step in flight
        (_admit: no settle; the first token is sampled and seated on
        the device), grows the pages for step k and dispatches it
        from the HOST's books (_Slot: a slot's write position counts
        its tokens in flight, a prefill's first among them, and a
        finish by max_new_tokens is known before the token is), and
        only then reads back, oldest first, what it owes: step k-1's
        tokens (_land), then each prefill's first token
        (_land_first), while the device computes step k. No phase
        before that readback reads from the device. Whatever edits
        slots outside this order (cancel, a dry pool's preemption,
        drain) first lands everything unread through _settle; an
        idle engine has nothing unread.

        With a draft model the step is serial: the first tokens land
        right after _admit, through the same code, and nothing is
        unread when the draft/verify round starts."""
        phases = self._phases
        self._admit()
        if self.speculative is not None:
            self._land_unread()
            seated = self._decoding()
            if seated:
                self._push_active(seated)
                self._finished += self._step_speculative(len(seated))
            if not any(s.request is not None for s in self._slots):
                self._settle("idle")    # nothing unread: a dry spell
            return
        if self.pages is not None:
            with phases("grow_pages"):
                # a drafting step writes its draft's row too; a block
                # pass its two blocks, a block further on if the pass
                # in flight closes one
                self._grow_pages(span=2 * self.block or self.drafts)
        seated = self._decoding()
        if not seated:
            # Every seated request waits for its last token only.
            self._settle("idle")
            return
        overlapped = self._in_flight is not None
        t0 = time.monotonic()
        self._dispatching(t0)
        with phases("dispatch"):
            self._push_active(seated)
            accepted = step_key = flags = None
            if self.block:
                # greedy: the key goes unsplit and unused
                (self.cache, self._tokens, self._positions, next_tok,
                 self._masked, flags, *chosen) = self._decode_step(
                    self.params, self.cache, self._tokens,
                    self._positions, self._active, self._key,
                    masked=self._masked)
            elif self.drafts:
                # greedy: the key goes unsplit and unused
                (self.cache, self._tokens, self._positions, first,
                 second, accepted, self._draft,
                 *chosen) = self._decode_step(
                    self.params, self.cache, self._tokens,
                    self._positions, self._active, self._key,
                    self._draft)
                next_tok = (first, second)
                del first, second
            else:
                self._key, step_key = jax.random.split(self._key)
                (self.cache, self._tokens, self._positions, next_tok,
                 *chosen) = self._decode_step(
                    self.params, self.cache, self._tokens,
                    self._positions, self._active, step_key)
            self._unread.append(_InFlight(
                next_tok, step_key, seated, t0, len(self._unread),
                *chosen, accepted=accepted, flags=flags))
            # _land lets the arrays die
            del next_tok, step_key, chosen, accepted, flags
            for i, _ in seated:
                self._slots[i].in_flight += self.block or 1 + self.drafts
                # (a block pass lands no token at least)
                self._slots[i].launches += not self.block
        self.decode_steps += 1
        self.steps_overlapped += overlapped
        self._land_unread(keep=1)
        if not any(s.request is not None for s in self._slots):
            # An eos ended the last request: the step in flight
            # computes overshoot alone.
            self._settle("idle")

    def _settle(self, cause: str) -> bool:
        """Read back and emit everything the device was handed and
        the host has not read (True if there was anything: a decode
        step in flight, prefills' first tokens), so that the host's
        books and the device agree and nothing is owed to any
        request: what everything that edits slots outside _step's
        own order calls first. ``cause`` is one of SETTLE_CAUSES,
        counted where a decode step was in flight (its successor
        will not overlap it). After an ``idle`` one the device has
        nothing to run, from the last landing until something is
        dispatched (_dispatching): no_work_seconds."""
        unread = bool(self._unread)
        if unread:
            if self._in_flight is not None:
                self.settles[cause] += 1
            self._land_unread()
        if cause == "idle" and self._dry_since is None:
            self._dry_since = self._landed_at
        return unread

    def _dispatching(self, now: float) -> None:
        """A program is about to be handed to the device: a dry
        spell, if the device was in one, ends here."""
        if self._dry_since is not None:
            dry = now - self._dry_since
            self.no_work_seconds += dry
            self._no_work_call += dry
            self._dry_since = None

    def _land_unread(self, keep: int = 0) -> None:
        """Land, in the order the device was handed them, all but the
        newest ``keep`` of the unread results."""
        while len(self._unread) > keep:
            result = self._unread.popleft()
            if isinstance(result, _InFlight):
                self._land(result)
            else:
                self._land_first(result)

    def _land_first(self, first: _FirstToken) -> None:
        """Wait for a dispatched prefill's first token and hand it
        over: the slot's books catch up (the token was counted in
        flight since _admit), the prefill's choices go on record, a
        request it ends (max_new_tokens, eos_id) is finished. The
        wait is the "prefill" phase's, the books and the hand-over
        "slot_update"'s, as when _admit did both."""
        phases = self._phases
        # a block-diffusion model's prefill leaves its whole blocks
        # alone in the cache, and its record holds those
        kept = _cached_len(
            self.config, first.first_position + first.prefilled) \
            - first.first_position
        with phases("prefill"):
            ready = first.token.is_ready()
            token = int(np.asarray(first.token)[0])
            chosen = (None if first.chosen is None else np.asarray(
                first.chosen)[:, :kept].astype(np.int16))
        # Nobody else sits here: whatever frees a slot lands what is
        # unread first.
        slot = self._slots[first.slot]
        request_id = slot.request.request_id
        self._landed(Launch.landing(
            "prefill", first.dispatched_at, self._landed_at, ready,
            first.queued, path=first.path, bucket=first.bucket,
            tokens=first.prefilled, request_id=request_id,
            road=self._experts_road(first.bucket),
            chunks=-(-first.bucket // (self.prefill_chunk
                                       or first.bucket))))
        with phases("slot_update"):
            if chosen is not None or self.block:
                self._decisions[request_id] = {
                    "first": first.first_position, "prefill": chosen,
                    "steps": [],
                    # (a block model's first generated position)
                    "start": first.first_position + kept}
            if not self.block:
                slot.in_flight -= 1
                slot.launches -= 1
                slot.generated.append(token)
                if slot.ended():
                    # A decode step dispatched behind the prefill with
                    # this slot seated computes overshoot (_land).
                    self._finish(first.slot)
                self._emit([(request_id, token,
                             len(slot.generated) - 1)])
            first.token = first.chosen = None   # as in _land

    def _land(self, step: _InFlight) -> None:
        """Wait for a dispatched step's tokens and emit them, each
        only to the request its slot held at dispatch: a request that
        ended on its eos_id one landing earlier (a step's token, or
        a prefill's first) was still decoded, and that token is
        dropped here (overshoot_tokens; its K/V row lies past the
        request's last token, in a page no index names). A drafting
        step (step.accepted) lands 1 + accepted tokens a slot, cut
        short where one of them is the request's last. Every
        program is ordered behind the one before it by the cache it
        consumes: a slot's next prefill behind this step, and the
        step behind a prefill dispatched before it (_admit)."""
        if step.flags is not None:
            return self._land_blocks(step)
        phases = self._phases
        with phases("readback"):
            # [B, 1 + drafts]: what each slot may land, in order
            if step.accepted is None:
                ready = step.tokens.is_ready()
                block = np.asarray(step.tokens)[:, None]
                accepted = None
            else:
                ready = step.tokens[0].is_ready()
                block = np.stack([np.asarray(tokens)
                                  for tokens in step.tokens], axis=1)
                accepted = np.asarray(step.accepted)
            # the whole step's choices once, [layers, B, 1 + drafts,
            # k]; take_decisions cuts a request's positions out of
            # them, if anyone asks
            chosen = None
            if step.chosen is not None:
                chosen = np.asarray(step.chosen).astype(np.int16)
                chosen = chosen.reshape(
                    len(chosen), *block.shape, chosen.shape[-1])
        rows = [i for i, _ in step.seated]
        taken = 0 if accepted is None else int(accepted[rows].sum())
        self.mtp_drafted += self.drafts * len(rows)
        self.mtp_accepted += taken
        self._landed(Launch.landing(
            "decode", step.dispatched_at, self._landed_at, ready,
            step.queued, rows=len(rows), tokens=len(rows) + taken,
            accepted=taken))
        with phases("emit"):
            tokens = block.tolist()
            batch = []
            for i, req in step.seated:
                slot = self._slots[i]
                count = 1 if accepted is None else 1 + int(accepted[i])
                if slot.request is not req:
                    self.overshoot_tokens += count
                    continue
                slot.in_flight -= block.shape[1]
                slot.launches -= 1
                record = self._decisions.get(req.request_id)
                served = 0
                # max_new_tokens and eos_id cut a landing short
                for token in tokens[i][:count]:
                    slot.generated.append(token)
                    served += 1
                    batch.append((req.request_id, token,
                                  len(slot.generated) - 1))
                    if slot.ended():
                        self.overshoot_tokens += count - served
                        self._finish(i)
                        break
                if chosen is not None:
                    # the choices at the positions the step FED and
                    # committed: that of the token before each one
                    # served
                    record["steps"].append((chosen, i, served))
            self._emit(batch)
            if chosen is not None:
                self._count_experts(chosen[:, rows].reshape(
                    len(chosen), -1, chosen.shape[-1]))
            # The step's device arrays die here, inside the phase:
            # their destructor releases the GIL, and whoever the
            # hand-over woke may take its turn then. It is emit's
            # cost, so it is counted here and not after every phase
            # has ended.
            step.tokens = step.key = step.chosen = step.accepted = None

    def _land_blocks(self, step: _InFlight) -> None:
        """_land for a block pass (_denoise_or_commit): a slot whose
        pass closed a block lands it (of the first generated block
        the positions past the prompt's given tokens), cut short where
        one of its tokens is the request's last (max_new_tokens, its
        eos_id: the block was finished, what lies behind is dropped);
        a slot whose pass did not lands nothing. A request ends on a
        landing alone, so the pass dispatched meanwhile with its slot
        seated is overshoot, as after an eos. Every pass goes on the
        request's record (take_decisions)."""
        phases = self._phases
        size = self.block
        with phases("readback"):
            ready = step.tokens.is_ready()
            # [B, block]: each slot's block as the pass found it
            blocks = np.asarray(step.tokens)[:, size:]
            flags = np.asarray(step.flags)              # [B, block + 1]
            chosen = None if step.chosen is None else np.asarray(
                step.chosen).astype(np.int16)  # [layers, B, 2 block, k]
        rows = [i for i, _ in step.seated]
        closed = flags[:, -1].astype(bool)
        closing = [i for i in rows if closed[i]]
        tokens = blocks.tolist()
        # what each closing slot serves of its block
        serves = {i: self._slots[i].served_of(
                      tokens[i][self._slots[i].given:])
                  for i, req in step.seated
                  if closed[i] and self._slots[i].request is req}
        landed = sum(map(len, serves.values()))
        self.block_denoise_passes += len(rows)
        self.block_commits_fused += len(closing)
        self.block_positions_unmasked += int(flags[rows, :-1].sum())
        self.block_tokens_landed += landed
        self._landed(Launch.landing(
            "decode", step.dispatched_at, self._landed_at, ready,
            step.queued, rows=len(rows), tokens=landed,
            commits=len(closing)))
        with phases("emit"):
            batch = []
            for i, req in step.seated:
                slot = self._slots[i]
                if slot.request is not req:
                    self.overshoot_tokens += size * bool(closed[i])
                    continue
                slot.in_flight -= size
                self._decisions[req.request_id]["steps"].append(
                    (chosen, i, flags[i],
                     blocks[i] if closed[i] else None))
                if not closed[i]:
                    continue
                given, slot.given = slot.given, 0
                for token in serves[i]:
                    slot.generated.append(token)
                    batch.append((req.request_id, token,
                                  len(slot.generated) - 1))
                if slot.ended():
                    self.overshoot_tokens += size - given - len(serves[i])
                    self._finish(i)
            self._emit(batch)
            if chosen is not None:
                # a closing slot's two blocks, a plain slot's first:
                # the dead half's rows chose for nobody
                self._count_experts(np.concatenate(
                    [chosen[:, rows, :size].reshape(
                        len(chosen), -1, chosen.shape[-1]),
                     chosen[:, closing, size:].reshape(
                         len(chosen), -1, chosen.shape[-1])], axis=1))
            # as in _land: the arrays die inside the phase
            step.tokens = step.key = step.chosen = step.flags = None

    def _emit(self, batch: list[tuple[str, int, int]]) -> None:
        """Hand the (request_id, token, index) triples a step or a
        prefill produced to the observer: on_tokens ONCE where it is
        set, else on_token a triple."""
        if not batch:
            return
        if self.on_tokens is not None:
            self.on_tokens(batch)
        elif self.on_token is not None:
            for triple in batch:
                self.on_token(*triple)

    def _step_speculative(self, rows: int
                          ) -> list[tuple[str, list[int]]]:
        """One ragged draft/verify/commit round (see the spec_step
        docstring for the compute): slots advance by different amounts
        per step, so the host bookkeeping below is variable-stride —
        each slot appends its own 1..gamma+1 committed tokens, with
        per-token eos/max_new checks so a slot can stop mid-block."""
        phases = self._phases
        if self.pages is not None:
            with phases("grow_pages"):
                self._grow_pages(span=self.gamma)
        t0 = time.monotonic()
        self._dispatching(t0)
        with phases("dispatch"):
            (self.cache, self._draft_cache, self._tokens,
             self._positions, block, a_slot) = self._spec_step(
                self.params, self._draft_params, self.cache,
                self._draft_cache, self._tokens, self._positions,
                self._active)
        with phases("readback"):
            ready = block.is_ready()
            block_host = np.asarray(block)
            # a draft/verify block is one launch of the ``rows``
            # slots it advances
            self._landed(Launch.landing(
                "decode", t0, self._landed_at, ready, 0, rows=rows))
            a_host = np.asarray(a_slot)
        emitted: list[tuple[str, list[int]]] = []
        batch = []
        n_active = 0
        with phases("emit"):
            for i, slot in enumerate(self._slots):
                req = slot.request
                if req is None:
                    continue
                n_active += 1
                accepted = int(a_host[i])
                self.spec_accepted += accepted
                for j in range(accepted + 1):
                    token = int(block_host[i, j])
                    slot.generated.append(token)
                    batch.append((req.request_id, token,
                                  len(slot.generated) - 1))
                    if slot.ended():
                        # Stopped mid-block: the remaining committed
                        # tokens are discarded (their cache rows
                        # recycle with the slot).
                        emitted.append((req.request_id,
                                        list(slot.generated)))
                        self._free_slot(i)
                        break
            self._emit(batch)
            del block, a_slot       # as in _land: the woken's turn
        self.spec_rounds += 1
        self.spec_proposed += self.gamma * n_active
        return emitted

    def occupancy(self) -> dict:
        """The engine's state at this moment, read-only: what a
        serve_step row records as its step begins and what /stats
        reports. Pages are counted once however many slots read them.
        Safe to call from another thread than the stepping one (the
        snapshot may then straddle a step). The page keys
        (kv_pages.PagePool.occupancy) are absent from a dense
        engine; state_slots_in_use / state_bytes_held (slots seated,
        and the per-slot state they hold) from a model without one;
        experts_held (expert matrices on this chip, over all routed
        layers) from a model without routed layers. A paged engine
        whose model has window layers adds their page group beside
        the pool's (kv_pages_* stay the FULL layers'):
        window_pages_in_use / window_pages_total (kv_pages.
        ring_occupancy), and the keys ONE full and ONE window layer's
        decode kernel attends in the step dispatched from this state:
        kv_tokens_full (= live_tokens; also of any engine whose model
        drafts for itself) and kv_tokens_window (each
        seated slot's newest ``window`` at most)."""
        held = [slot.held_tokens() for slot in self._slots
                if slot.decoding()]
        out = {"slots_active": len(held),
               "slots_total": self.num_slots,
               "queued": len(self._queue), "live_tokens": sum(held)}
        if self.pages is not None:
            out.update(self.pages.occupancy(held))
            if self.drafts:
                # the keys ONE full layer's verify call attends at its
                # first position, window layers or none
                out["kv_tokens_full"] = sum(held)
            if self.window:
                out.update(kv_pages.ring_occupancy(
                    held, self.num_slots, self.page_size,
                    tfm.ring_pages(self.config, self.window),
                    self.window))
        if self.stateful:
            seated = sum(slot.request is not None
                         for slot in self._slots)
            out["state_slots_in_use"] = seated
            out["state_bytes_held"] = seated * self._slot_state_bytes
        if self._decision_layers:
            out["experts_held"] = (len(self._decision_layers)
                                   * self.config.experts.held)
        return out

    def step_stats(self) -> dict:
        """Cumulative step counters since the engine was built: steps
        that admitted, decoded or read a step back, their wall
        seconds, the lookahead's counters (_lookahead_counts), a
        routed model's expert counters (_count_experts), a drafting
        model's mtp_drafted / mtp_accepted (_mtp_counts), a
        block-diffusion model's passes (_block_counts), the seconds
        of each phase, and the process's compile count
        (programs built or loaded from the persistent cache, and
        their seconds)."""
        compiles, compile_seconds = self._compiles.read()
        return {"steps": self.steps_total,
                "step_seconds": self.step_seconds_total,
                **self._lookahead_counts(),
                **self._launch_counts(),
                **(self._expert_counts() if self._decision_layers
                   else {}),
                **(self._mtp_counts() if self.drafts else {}),
                **(self._block_counts() if self.block else {}),
                "phase_seconds": dict(self._phases.total),
                "compiles": compiles,
                "compile_seconds": compile_seconds}

    def _launch_counts(self) -> dict:
        """The device's timeline as the landings gave it (_landed),
        cumulative: launches landed, the seconds they held the
        device's queue and the landings that found their result
        ready (the host, not the device, set their pace), each by
        kind (LAUNCH_KINDS); the prefills' padded and unpadded
        tokens, and how many of them ran their routed experts by the
        grouped road (Launch.road: every launch of a bucket above
        moe.experts_road's crossover; 0 for a model without routed
        layers); the seconds the device had nothing to run because
        nothing was there (from an idle settle's last landing, or
        the engine's construction, to the next dispatch); and the
        stall records written."""
        return {"launches": dict(self.launches),
                "launch_seconds": dict(self.launch_seconds),
                "landings_ready": dict(self.landings_ready),
                "prefill_bucket_tokens": self.prefill_bucket_tokens,
                "prefill_tokens": self.prefill_tokens,
                "prefills_grouped": self.prefills_grouped,
                "no_work_seconds": self.no_work_seconds,
                "stalls": self.stalls}

    def spec_stats(self) -> Optional[dict]:
        """Speculative-decode counters, or None when no draft model
        is configured. acceptance_rate = accepted/proposed is the
        measured draft quality; tokens-per-target-forward is
        1 + acceptance_rate * gamma."""
        if self.speculative is None:
            return None
        return {
            "gamma": self.gamma,
            "rounds": self.spec_rounds,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "acceptance_rate": (
                self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0),
        }

    def _finish(self, i: int) -> None:
        """Slot i's request has its last token: it goes to the
        finished list (its record of choices to where take_decisions
        finds it) and the slot is free."""
        slot = self._slots[i]
        request_id = slot.request.request_id
        self._finished.append((request_id, list(slot.generated)))
        record = self._decisions.pop(request_id, None)
        if record is not None:
            self._decisions_done[request_id] = record
            while len(self._decisions_done) > self._DECISIONS_KEPT:
                self._decisions_done.popitem(last=False)
        self._free_slot(i)

    def _free_slot(self, i: int) -> None:
        """The slot gives back what it holds. Its per-slot state (a
        stateful model's) stays where it is: the next seat overwrites
        it, and until then no step reads it for anybody."""
        request = self._slots[i].request
        if request is not None:
            # cancelled or preempted: a resumption records anew
            self._decisions.pop(request.request_id, None)
        self._slots[i] = _Slot()
        if self.pages is not None:
            self.pages.release(i)
            self._push_tables()

    # Finished requests whose record nobody has taken yet: the oldest
    # are dropped beyond this many.
    _DECISIONS_KEPT = 4096

    def take_decisions(self, request_id: str) -> Optional[dict]:
        """A FINISHED request's record of what the model's routed
        layers chose, handed over once: {"first": p, "layers": {layer
        name: int32 [m, k]}}, the expert indices (over all the
        router's outputs, held here or not) that the steps which
        served it computed at positions p .. p+m-1 of prompt + served
        tokens: the prefill's for the positions it ran, each decode
        step's for the position it fed (every position but the last
        token's, which is never fed; of a drafting step the COMMITTED
        positions alone, and under the name transformer.MTP_NAME the
        module's own routed layer at the same positions). ``first``
        is past whatever
        prefix came out of shared pages. None for an unknown id, a
        second call, or a model without such layers. A model that
        generates by diffusion over blocks has a record whether it
        routes or not, of another shape: _block_record."""
        record = self._decisions_done.pop(request_id, None)
        if record is None:
            return None
        if self.block:
            return self._block_record(record)
        rows = np.concatenate(
            [record["prefill"]] + [step[:, i, :served]
                                   for step, i, served
                                   in record["steps"]],
            axis=1).astype(np.int32)
        return {"first": record["first"],
                "layers": dict(zip(self._decision_layers, rows))}

    def _block_record(self, record: dict) -> dict:
        """take_decisions of a model that generates by diffusion over
        blocks: {"first": p, "layers": {...}, "block": its length,
        "start": the first generated block's first position, "tokens":
        int32, every token of the committed blocks from ``start`` on as
        the program conditioned on them (the prompt's given ones and
        those dropped behind the request's last included)}. ``layers``
        holds, each int32 [m, .] over the positions p .. p+m-1 the
        prefill kept and the closing passes wrote:

          <routed layer>            the choices of the pass that wrote
                                    the position's K/V (the prefill, a
                                    closing pass's first half)
          <routed layer>.pass<s>    those of the block's s-th denoise
                                    pass (s < steps; pass 0 of every
                                    block but a request's first is its
                                    predecessor's closing pass's second
                                    half); a pass the block did not
                                    take saw the block without a mask,
                                    as the half that closed it did, and
                                    holds that half's; so do the
                                    prefill's positions
          unmask  [m, 1]            the denoise pass that unmasked the
                                    position; ``steps`` where it never
                                    was masked (the prompt's)

        Passes behind the last closed block (the block its closing
        pass opened, overshoot) are not on it."""
        size, steps = self.block, self.config.block_diffusion.steps
        prefill = record["prefill"]
        kept = record["start"] - record["first"]
        routed = prefill is not None
        written = [prefill] if routed else []
        passes = [[prefill] for _ in range(steps)] if routed else []
        unmask = [np.full((kept,), steps, np.int32)]
        tokens, denoised = [], []
        for chosen, i, flags, closed in record["steps"]:
            # [layers, 2 * block, k]: the open half is the second of a
            # pass that closed a block, else the first
            mine = chosen[:, i] if routed else None
            if closed is not None:
                at = np.full((size,), steps, np.int32)
                for s, (_, picked) in enumerate(denoised):
                    at[picked] = s
                unmask.append(at)
                tokens.append(closed)
                if routed:
                    written.append(mine[:, :size])
                    for s in range(steps):
                        passes[s].append(denoised[s][0]
                                         if s < len(denoised)
                                         else mine[:, :size])
                    mine = mine[:, size:]
                denoised = []
            denoised.append((mine[:, :size] if routed else None,
                             flags[:-1].astype(bool)))
        layers = {UNMASK_NAME: np.concatenate(unmask)[:, None]}
        # (no routed layer: no entry but the unmask passes)
        for s, parts in enumerate([written] + passes if routed else []):
            for name, rows in zip(self._decision_layers, np.concatenate(
                    parts, axis=1).astype(np.int32)):
                layers[pass_layer_name(name, s - 1) if s else name] = rows
        return {"first": record["first"], "layers": layers,
                "block": size, "start": record["start"],
                "tokens": np.concatenate(tokens).astype(np.int32)
                if tokens else np.zeros((0,), np.int32)}

    def _count_experts(self, chosen) -> None:
        """A landed decode step's (row, choice) pairs, int [decision
        layers, rows decoded, k]: how many there were, how many fell
        on experts held here (and were computed), and how many held
        experts had at least one."""
        experts = self.config.experts
        local = chosen.astype(np.int32) - experts.first_expert
        here = (local >= 0) & (local < experts.held)
        self.expert_pairs_chosen += int(chosen.size)
        self.expert_pairs_here += int(here.sum())
        # distinct (layer, held expert) pairs
        layer = np.arange(len(local))[:, None, None] * experts.held
        self.experts_hit += len(np.unique((local + layer)[here]))

    def _mtp_counts(self) -> dict:
        """A drafting engine's cumulative counters: drafts the landed
        decode steps verified (drafts x the slots each advanced) and
        how many of them the stack's own choice confirmed (each one
        more token landed by the same step)."""
        return {"mtp_drafted": self.mtp_drafted,
                "mtp_accepted": self.mtp_accepted}

    def _block_counts(self) -> dict:
        """A block-diffusion engine's cumulative counters, of the
        landed block passes: the (slot, pass) pairs that denoised
        (every one: a pass that closes a block denoises the next) and
        that did nothing but commit (none), the positions the passes
        unmasked, the tokens the closed blocks landed (a first block's
        given positions not counted), and the blocks closed by a pass
        that denoised the next. Tokens over passes is what the
        schedule is worth: ``block`` over ``steps`` at the static
        rule's floor."""
        return {name: getattr(self, name) for name in BLOCK_COUNTERS}

    def _expert_counts(self) -> dict:
        return {"expert_pairs_here": self.expert_pairs_here,
                "expert_pairs_chosen": self.expert_pairs_chosen,
                "experts_hit": self.experts_hit}

    def prefix_cache_clear(self) -> int:
        """kv_pages.PagePool.clear_unreferenced: the pages reclaimed."""
        return self.pages.clear_unreferenced()

    def prefix_stats(self) -> Optional[dict]:
        """kv_pages.PagePool.stats, or None when disabled."""
        return self.pages.stats() if self.prefix_cache else None

    def slo_stats(self) -> dict:
        """SLO scheduling counters + the live cost estimates
        admission decides with."""
        return {
            "sheds": self.slo_sheds,
            "sheds_by_class": dict(self.sheds_by_class),
            "deferrals": self.slo_deferrals,
            "prefill_ms_per_token": self._prefill_ms_per_token,
            "step_ms": self._step_ms,
        }

    def _grow_pages(self, span: int = 0) -> None:
        """Have the pool cover every active slot's next write
        positions pos..pos+span (PagePool.grow), by the host's books,
        and push the tables if a row changed. Under overcommit a dry
        pool first lands what is unread (the step in flight, the
        first tokens of this call's prefills), which may give pages
        back, then preempts a victim (whose slot the loop then
        skips), and asks again each time."""
        changed = False
        for i in range(self.num_slots):
            # Read anew each time round: a settle can finish and a
            # preemption evict whoever sat here.
            while (slot := self._slots[i]).decoding():
                req = slot.request
                try:
                    changed |= self.pages.grow(
                        i, slot.held_tokens() - 1, span,
                        len(req.prompt) + req.max_new_tokens)
                    break
                except kv_pages.PoolDry:
                    # What is unread may finish a request and give
                    # its pages back: land it, then ask again, before
                    # anybody is evicted.
                    if not self._settle("preempt"):
                        self._preempt(exclude=i)
        if changed:
            self._push_tables()

    def _preempt(self, exclude: int) -> int:
        """Evict the active slot with the fewest generated tokens
        (cheapest re-prefill), reclaim its pages, and re-queue its
        request AT THE HEAD with its generated-so-far tokens so
        resumption re-prefills prompt+generated and continues — the
        greedy continuation is unchanged. Nothing is unread here
        (_grow_pages settles before it evicts: the step in flight and
        every first token have landed), so the victim's generated
        list is all it was served. Returns the victim index."""
        candidates = [
            j for j in range(self.num_slots)
            if j != exclude and self._slots[j].request is not None]
        if not candidates:
            raise RuntimeError(
                "paged KV pool exhausted with no preemptible slot — "
                "a single request's live context exceeds the pool")
        victim = min(candidates,
                     key=lambda j: len(self._slots[j].generated))
        slot = self._slots[victim]
        # Preempted work resumes at the HEAD of its own priority
        # class: ahead of waiting peers (it owns partial progress) but
        # never ahead of strictly higher-priority entries — a plain
        # head insert would let a low-priority victim starve a queued
        # high-priority request under sustained page pressure.
        entry = _QueueEntry(slot.request, list(slot.generated))
        pos = 0
        while (pos < len(self._queue) and
               self._queue[pos].request.priority >
               slot.request.priority):
            pos += 1
        self._queue.insert(pos, entry)
        self.preemptions += 1
        self._free_slot(victim)
        return victim

    def _put(self, host_array):
        """Host array -> the engine's device (the default device when
        the engine is not pinned)."""
        return jax.device_put(host_array, self.device)

    def _decoding(self) -> list[tuple[int, Request]]:
        """(slot, request) for every slot the next decode step
        advances (_Slot.decoding), from the host's books."""
        return [(i, slot.request)
                for i, slot in enumerate(self._slots)
                if slot.decoding()]

    def _push_active(self, seated: list[tuple[int, Request]]) -> None:
        """The step programs' ``active`` mask for a step that
        advances ``seated``. Sent only when it differs from what the
        device has."""
        mask = np.zeros((self.num_slots,), np.bool_)
        mask[[i for i, _ in seated]] = True
        if not np.array_equal(mask, self._active_host):
            self._active_host = mask
            self._active = self._put(mask)

    def _push_tables(self) -> None:
        """Write the canonical block table into the cache copy of
        every layer that reads the page pool through one (a window
        layer's ring is the slot's own: no table, nothing to push) — a
        device buffer of its own for each layer, because the
        step programs donate the cache and one buffer under every
        layer's leaf would be donated once per layer ("Attempt to
        donate the same buffer twice"). One transfer and one small
        program (_table_per_layer), not a transfer per layer."""
        tables = iter(_table_per_layer(
            self._put(self.pages.table),
            tfm.paged_layer_count(self.config)))

        def push(leaf_dict):
            if isinstance(leaf_dict, dict) and \
                    "block_table" in leaf_dict:
                return {**leaf_dict, "block_table": next(tables)}
            if isinstance(leaf_dict, dict):
                return {k: push(v) for k, v in leaf_dict.items()}
            return leaf_dict

        self.cache = push(self.cache)

    # ----------------------------- internal ----------------------------

    def _bucket_length(self, n: int) -> int:
        """Round a prompt length up to its compile bucket (the next
        power of two, floored at 16, capped at max_decode_len): one
        prefill compile per bucket instead of per distinct length."""
        bucket = 16
        while bucket < n:
            bucket *= 2
        return min(bucket, self.max_decode_len)

    def _enqueue(self, entry: "_QueueEntry") -> None:
        """Insert keeping the queue sorted by descending priority,
        then earliest TTFT deadline within a priority class (EDF;
        entries without a target sort last and stay FIFO among
        themselves — with no SLO targets anywhere this is exactly
        the old priority+FIFO order)."""
        priority = entry.request.priority
        deadline = self._ttft_deadline(entry)
        deadline = float("inf") if deadline is None else deadline
        for k in range(len(self._queue) - 1, -1, -1):
            other = self._queue[k]
            other_deadline = self._ttft_deadline(other)
            if other_deadline is None:
                other_deadline = float("inf")
            if (other.request.priority > priority or
                    (other.request.priority == priority and
                     other_deadline <= deadline)):
                self._queue.insert(k + 1, entry)
                return
        self._queue.insert(0, entry)

    def _ttft_deadline(self, entry: "_QueueEntry") -> Optional[float]:
        """Absolute (monotonic-clock) TTFT deadline, or None when the
        request carries no target."""
        target = entry.request.ttft_target_ms
        if target is None:
            return None
        return entry.submitted_at + target / 1000.0

    def _shed_expired(self, now: float) -> None:
        """Overload shedding (armed by slo_shed_grace_ms): drop every
        queued entry whose TTFT deadline is blown by more than the
        grace, deepest violation first — serving it would be pure
        badput while fresher requests still have budget. Preempted
        (resumed) entries are exempt: their first token already
        shipped, so their TTFT is history and their partial work
        would be wasted."""
        if self.slo_shed_grace_ms is None or self.draining:
            # Draining owns the queue: drain() already evicted it for
            # failover, and anything a draining replica can still
            # finish must not be shed out from under the router.
            return
        while True:
            worst_k, worst_over = None, 0.0
            for k, entry in enumerate(self._queue):
                if entry.resumed:
                    continue
                deadline = self._ttft_deadline(entry)
                if deadline is None:
                    continue
                over = ((now - deadline) * 1000.0 -
                        self.slo_shed_grace_ms)
                if over > worst_over:
                    worst_k, worst_over = k, over
            if worst_k is None:
                return
            entry = self._queue.pop(worst_k)
            self.slo_sheds += 1
            cls = entry.request.slo_class
            self.sheds_by_class[cls] = \
                self.sheds_by_class.get(cls, 0) + 1
            if self.on_shed is not None:
                self.on_shed(entry.request.request_id,
                             "ttft deadline exceeded")

    def _should_defer(self, entry: "_QueueEntry",
                      now: float) -> bool:
        """Batch-composition guard: admitting a long prompt stalls
        every active decode for its whole prefill. When that
        predicted stall (live EWMA prefill cost x bucket length)
        exceeds tpot_stall_factor x the tightest active TPOT target,
        hold the candidate back — unless its own TTFT deadline would
        blow while waiting, at which point its SLO outranks the
        actives' headroom."""
        if self._prefill_ms_per_token is None:
            return False
        targets = [
            s.request.tpot_target_ms for s in self._slots
            if s.request is not None and
            s.request.tpot_target_ms is not None]
        if not targets:
            return False
        tokens = len(entry.request.prompt) + len(entry.resumed)
        if self.prefix_cache and not self._recomputes_matched:
            # Predict the POST-MATCH suffix cost: a cached prefix
            # pays a gather, not a prefill.
            tokens -= self.pages.cached_tokens(
                entry.request.prompt + entry.resumed)
        stall = self._bucket_length(tokens) * \
            self._prefill_ms_per_token
        if stall <= min(targets) * self.tpot_stall_factor:
            return False
        deadline = self._ttft_deadline(entry)
        if deadline is not None and \
                now + stall / 1000.0 >= deadline:
            return False
        return True

    def _landed(self, launch: Launch) -> None:
        """A launch has landed: the one place its record goes from.
        The cumulative counters (step_stats), the estimates
        admission decides with (slo_stats, _should_defer: an EWMA of
        the decode step's period and one of the prefill's period per
        bucket token, the first decode sample and the first of each
        (path, bucket) left out: they measure a compile), the ring,
        the list the call's row takes, and a stall record where the
        launch held the device's queue for longer than STALL_MS."""
        kind = launch.kind
        self._landed_at = launch.landed_at
        self.launches[kind] += 1
        self.launch_seconds[kind] += launch.period_ms / 1e3
        self.landings_ready[kind] += launch.ready
        if kind == "prefill":
            self.prefill_bucket_tokens += launch.bucket
            self.prefill_tokens += launch.tokens
            self.prefills_grouped += launch.road == "grouped"
            shape = (launch.path, launch.bucket)
            if shape not in self._prefill_shapes:
                self._prefill_shapes.add(shape)
            else:
                self._prefill_ms_per_token = _ewma(
                    self._prefill_ms_per_token,
                    launch.period_ms / max(1, launch.bucket))
        elif self.launches[kind] > 1:
            self._step_ms = _ewma(self._step_ms, launch.period_ms)
        self._ring.append(launch)
        self._landed_call.append(launch)
        then, now = self._host_then, _HostCounters.read(self._compiles)
        self._host_then = now
        if launch.period_ms > STALL_MS:
            self._stalled(
                then, now, kind=kind, ready=launch.ready,
                wait="prefill" if kind == "prefill" else "readback",
                launch=launch.entry())

    def _host_stalled(self, call_ms: float, landed0: int,
                      then: _HostCounters) -> None:
        """The host form of a stall record, for a call of step()
        that lasted more than STALL_MS / 4 and during which no launch
        stalled: written where more than that much of the call lies
        under no landing (a call that lands three long prefills is
        the device's time, not the host's), with the phase that held
        the most of it. ``landed0``: how many of _landed_call were
        there when the call began; ``then``: the host's counters as
        of the last landing before it."""
        landed_ms = sum(launch.period_ms
                        for launch in self._landed_call[landed0:])
        if call_ms - landed_ms <= STALL_MS / 4:
            return
        phase_ms = {name: seconds * 1e3
                    for name, seconds in self._phases.step.items()}
        self._stalled(
            then, _HostCounters.read(self._compiles), kind="host",
            call_ms=call_ms, landed_ms=landed_ms,
            phase=max(phase_ms, key=phase_ms.get, default=None),
            phase_ms=phase_ms)

    def _stalled(self, then: _HostCounters, now: _HostCounters,
                 **what) -> None:
        """Write one serve_stall record: ``what`` (the launch that
        landed late and the wait that ended it, or the call the host
        was late in), what the host did between ``then`` and ``now``,
        and the ring. One JSON line to the logger at WARNING, a
        serve_stall row where the recorder is on, and one more of
        ``stalls``. An interval in which the process compiled a
        program is no stall: ``compiles`` and ``compile_seconds``
        have it. How to read one: docs/32-tracing.md."""
        if now.compiles > then.compiles:
            return
        self.stalls += 1
        interval_s = now.at - then.at
        record = {
            **what, "interval_ms": interval_s * 1e3,
            # thread_cpu_s near 0: blocked on the runtime; near the
            # interval: the engine thread was working or spinning
            **{name: getattr(now, name) - getattr(then, name)
               for name in ("thread_cpu_s", "ru_utime_s", "ru_stime_s",
                            "ru_nivcsw", "ru_majflt")},
            "gc_collections": [a - b for a, b in zip(
                now.gc_collections, then.gc_collections)],
            "loadavg": list(os.getloadavg()),
            "threads": threading.active_count(),
            "ring": [launch.entry() for launch in self._ring]}
        logger.warning("serve_stall %s", json.dumps(record))
        wall = time.time()
        trace_spans.record(trace_spans.SPAN_SERVE_STALL,
                           wall - interval_s, wall, **record)

    def _experts_road(self, bucket: int) -> str:
        """The road a prefill program of this bucket takes through
        its routed experts: moe.experts_road asked as the layer asks
        it while the program is traced, with the rows of one prefill
        segment (_prefill_segments); "" for a model without routed
        layers."""
        if not self._decision_layers:
            return ""
        return moe.experts_road(
            min(self.prefill_chunk or bucket, bucket),
            self.config.experts)

    def _padded(self, tokens: list[int]):
        """tokens, zero-padded to their compile bucket: [1, bucket]."""
        pad = self._bucket_length(len(tokens)) - len(tokens)
        return self._put(np.asarray([tokens + [0] * pad], np.int32))

    def _prefill_call(self, slot: int, tokens: list[int], prompt,
                      seat: Optional[kv_pages.Seat]) -> tuple:
        """A seated request's path (a serve_step row's "path"),
        compile bucket and tokens to prefill, then the program and
        its arguments after (params, cache): dense without a pool,
        shared over the suffix when the seat matched pages of the
        index, else the cold paged one ("recomputed": a stateful
        model's whole prompt over matched pages)."""
        if seat is None:
            return ("dense", prompt.shape[1], len(tokens),
                    self._prefill, (slot, prompt, len(tokens)))
        if not seat.matched:
            return ("cold", prompt.shape[1], len(tokens),
                    self._prefill_paged,
                    (slot, prompt, self._put(seat.row), len(tokens)))
        if self._recomputes_matched:
            # The state at the prompt's end (a window layer's ring:
            # its newest keys) is of no page, so the
            # whole prompt runs again, down the cold program. Its row
            # names the scratch page where the seat matched: what it
            # computes for the shared pages (immutable) is dropped,
            # and _admit then pushes the slot's true row.
            write = seat.row.copy()
            write[:seat.matched] = self.pages.scratch_page
            return ("recomputed", prompt.shape[1], len(tokens),
                    self._prefill_paged,
                    (slot, prompt, self._put(write), len(tokens)))
        suffix = self._padded(tokens[seat.prefix_len:])
        return ("shared", suffix.shape[1],
                len(tokens) - seat.prefix_len, self._prefill_shared,
                (slot, suffix, self._put(seat.prefix_ids),
                 self._put(seat.row), self._put(seat.suffix_row),
                 seat.prefix_len, len(tokens)))

    def _admit(self) -> None:
        """Seat queued requests in free slots: for each, the pool's
        seat and the puts ("admit"), the prefill program dispatched
        onto the cache as it stands, which may be the result of a
        decode step still in flight or of the prefill before this one
        (the runtime orders them by the cache each consumes: no
        settle), the seat program behind it (_seat_first), and the
        slot record with the first token counted in flight. Nothing
        here reads from the device: the first tokens are landed by
        whoever lands the unread results next (_step after it has
        dispatched the decode step, or a settle)."""
        if self.draining:
            # Drain ladder: no new admissions once the preempt/evict
            # notice lands — active slots finish, the queue was
            # already evicted by drain().
            return
        phases = self._phases
        now = time.monotonic()
        with phases("admit"):
            self._shed_expired(now)
        for i, slot in enumerate(self._slots):
            if slot.request is not None or not self._queue:
                continue
            # Per request: "admit" is the host's work to seat it
            # (deferral, the pool's seat, the puts), "prefill" the
            # dispatch of the prefill program (and, in _land_first,
            # the wait for its first token), "slot_update" the seat
            # program's dispatch and the slot record.
            with phases("admit"):
                entry = self._queue[0]
                req = entry.request
                if self._should_defer(entry, now):
                    # Head-of-line hold: admitting now would stall
                    # active decodes past their TPOT headroom.
                    self.slo_deferrals += 1
                    break
                # Resumed (preempted) requests re-prefill prompt +
                # what they had already generated, in one pass.
                tokens = req.prompt + entry.resumed
                prompt = self._padded(tokens)
                seat = None
                if self.pages is not None:
                    seat = self.pages.seat(
                        i, tokens,
                        req.max_new_tokens - len(entry.resumed))
                    if seat is None:
                        # No room yet: the head waits for frees.
                        break
                self._queue.pop(0)
                if self.on_admit is not None:
                    self.on_admit(req.request_id)
                path, bucket, prefilled, prefill, prefill_args = \
                    self._prefill_call(i, tokens, prompt, seat)
                self._admitted.append({
                    "request_id": req.request_id, "path": path,
                    "bucket": bucket, "tokens": prefilled})
            with phases("prefill"):
                self.prefills += 1
                # behind a decode step in flight, or behind another
                # prefill of this call
                queued = len(self._unread)
                self.prefills_overlapped += bool(queued)
                t0 = time.monotonic()
                self._dispatching(t0)
                self.cache, last_logits, *chosen = prefill(
                    self.params, self.cache, *prefill_args)
                # a drafting model's first draft, before the choices
                draft = chosen.pop(0) if self.drafts else None
                if seat is not None:
                    self.pages.publish(i, seat)
                if path == "recomputed":
                    self._push_tables()
                if self.speculative is not None:
                    # The draft cache must hold the same committed
                    # prefix (the spec-step invariant); its prefill
                    # logits are discarded — the first token is always
                    # sampled from the TARGET's prefill.
                    self._draft_cache, _ = self._draft_prefill(
                        self._draft_params, self._draft_cache, i,
                        prompt, len(tokens))
            with phases("slot_update"):
                if self.block:
                    # No first token: the device seats the first
                    # generated block, the prompt's tokens past its
                    # whole blocks given and the rest masked (resumed
                    # tokens count as the prompt's: a block they end
                    # in is opened again with them given).
                    start = _cached_len(self.config, len(tokens))
                    opening = np.zeros((self.block,), np.int32)
                    opening[:len(tokens) - start] = tokens[start:]
                    (self._tokens, self._masked, self._positions,
                     first) = _seat_block(
                        last_logits, self._tokens, self._masked,
                        self._positions, i, self._put(opening),
                        len(tokens) - start, start)
                    self._slots[i] = _Slot(
                        request=req, generated=list(entry.resumed),
                        given=len(tokens) - start)
                else:
                    # The prefill-sampled token IS the next generated
                    # token: the device seats it, the books count it
                    # in flight until _land_first has read it.
                    (self._key, self._tokens, self._positions,
                     first) = self._seat_first(
                        last_logits, self._key, self._tokens,
                        self._positions, i, len(tokens))
                    if draft is not None:
                        self._draft = _seat_draft(self._draft, i, draft)
                    self._slots[i] = _Slot(
                        request=req, generated=list(entry.resumed),
                        in_flight=1, launches=1)
                self._unread.append(_FirstToken(
                    first, i, path, bucket, t0, queued, *chosen,
                    prefilled=prefilled,
                    # the positions the prefill ran, past any prefix
                    # it took from shared pages
                    first_position=len(tokens) - prefilled))
