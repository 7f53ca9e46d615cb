"""Generation by diffusion over blocks through the engine
(TransformerConfig.block_diffusion; serving._denoise_or_commit), at a
small size on the CPU with seeded weights, against the plain reference
benchmark/reference/sdar_plain.py: the prefill of a prompt's whole
blocks, then a block denoised pass by pass and committed by the pass
that opens the next one, for every remainder of the prompt's length
mod the block and both remasking rules; what the schedule costs; a
request that ends inside a block, an eos inside one, a closing slot
beside a plain one in one step, a first block closed at once, a slot
preempted in mid-block, what streams; and the record the engine hands
over."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.serving import ContinuousBatcher, Request
from benchmark import harness, spec, weights
from benchmark.reference import sdar_plain as plain

MODULE = spec.load_module(spec.ROOT, spec.load_benchmark(),
                          "models/moe_block_diffusion.py")
BLOCK, STEPS, MASK = 4, 4, 255


def _file(remask="low_confidence_static", steps=STEPS):
    """The benchmark's configuration at its rehearsal's size, under
    one remasking rule."""
    model = harness.merged(
        spec.load_config("sdar-30b-a3b-chat-serve-1chip"), True)
    model["generation"] = {**model["generation"], "remasking": remask,
                           "denoising_steps": steps}
    assert (model["generation"]["block_length"],
            model["generation"]["mask_token_id"]) == (BLOCK, MASK)
    return model


def _built(remask="low_confidence_static", head_gain=1.0, steps=STEPS,
           seed=3):
    """(file, dims, float32 params, the program's float32 config).
    ``head_gain`` widens the head's kernel, so that confidences pass
    the dynamic rule's threshold and a pass unmasks more than one."""
    model = _file(remask, steps)
    dims = MODULE.dims(model)
    params = weights.make_params(MODULE.param_leaves(dims), seed,
                                 jnp.float32)
    params["lm_head"]["kernel"] = params["lm_head"]["kernel"] * head_gain
    config = dataclasses.replace(
        MODULE.program_model(model, dims, {"max_decode_len": 128}),
        dtype=jnp.float32, param_dtype=jnp.float32)
    return model, dims, params, config


def _engine(config, params, **kwargs):
    kwargs = {"num_slots": 3, "max_decode_len": 128, "kv_page_size": 16,
              "kv_num_pages": 40, **kwargs}
    return ContinuousBatcher(config, params, **kwargs)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 256, n)] for n in lengths]


def _drain(engine, watch=None):
    done = {}
    while engine.pending():
        if watch is not None:
            watch(engine)
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    return done


# every remainder of the prompt's length mod the block (5, 14, 19 and
# 8, 32: remainders 1, 2, 3, 0, 0), a prompt shorter than a block (3),
# and outputs that end inside a block (9, 7, 5, 6: not multiples of 4)
LENGTHS = (5, 8, 14, 19, 32, 3)
NEW = (9, 4, 7, 12, 5, 6)
RULES = {"static": ("low_confidence_static", 1.0),
         "dynamic_at_the_floor": ("low_confidence_dynamic", 1.0),
         "dynamic_above_it": ("low_confidence_dynamic", 40.0)}


@pytest.fixture(scope="module", params=sorted(RULES))
def served(request):
    """Six requests through one engine of three slots under one rule,
    with what every pass of slot 0 read (its block, its mask, its
    first position) and the logits the engine's own model gives for
    the pass's two blocks from the engine's own cache, taken before
    each step."""
    remask, gain = RULES[request.param]
    model, dims, params, config = _built(remask, gain)
    engine = _engine(config, params)
    prompts = _prompts(LENGTHS)
    for i, (prompt, new) in enumerate(zip(prompts, NEW)):
        engine.submit(Request(f"r{i}", prompt, new))
    passes = []

    def watch(engine):
        slot = engine._slots[0]
        if slot.request is None or not slot.decoding():
            return
        block = np.asarray(engine._tokens)[0, 0, :BLOCK]
        masked = np.asarray(engine._masked)[0]
        start = int(np.asarray(engine._positions)[0])
        # the pass the next step runs for slot 0, by the engine's model
        # on the engine's cache (a copy: apply donates nothing): the
        # open block, mask token where masked, and a block of masks
        logits, _ = engine.model.apply(
            {"params": params, "cache": engine.cache},
            jnp.concatenate(
                [jnp.where(engine._masked, MASK,
                           engine._tokens[:, 0, :BLOCK]),
                 jnp.full_like(engine._tokens[:, 0, BLOCK:], MASK)],
                axis=1),
            positions=engine._positions[:, None]
            + jnp.arange(2 * BLOCK)[None], mutable=serving._MUTABLE)
        passes.append((slot.request.request_id, start, masked.copy(),
                       block.copy(), np.asarray(logits[0], np.float32)))

    done = _drain(engine, watch)
    records = {f"r{i}": engine.take_decisions(f"r{i}")
               for i in range(len(prompts))}
    return (request.param, model, dims, params, engine, prompts, done,
            records, passes)


def test_every_request_is_served_whole_and_the_pool_is_clean(served):
    _rule, _model, _dims, _params, engine, prompts, done, records, _ = \
        served
    for i, new in enumerate(NEW):
        assert len(done[f"r{i}"]) == new
        record = records[f"r{i}"]
        start = len(prompts[i]) // BLOCK * BLOCK
        given = len(prompts[i]) - start
        assert record["start"] == start and record["block"] == BLOCK
        # every committed block whole, the prompt's given tokens first,
        # what was dropped behind the request's last token last
        assert len(record["tokens"]) % BLOCK == 0
        assert record["tokens"][:given].tolist() == prompts[i][start:]
        assert record["tokens"][given:given + new].tolist() == \
            done[f"r{i}"]
        assert len(record["tokens"]) == -(-(given + new) // BLOCK) * BLOCK
    engine.pages.check()
    assert engine.occupancy()["kv_pages_in_use"] == 0
    assert engine.take_decisions("r0") is None      # handed over once


def test_the_record_names_every_pass(served):
    rule, _model, dims, _params, engine, prompts, done, records, _ = \
        served
    stats = engine.step_stats()
    blocks = sum(len(r["tokens"]) // BLOCK for r in records.values())
    generated = 0
    for i, record in enumerate(records.values()):
        at = record["layers"]["unmask"][:, 0]
        kept = record["start"] - record["first"]
        given = len(prompts[i]) - record["start"]
        assert (at[:kept + given] == STEPS).all()    # never masked
        assert ((at[kept + given:] >= 0)
                & (at[kept + given:] < STEPS)).all()
        generated += len(at) - kept - given
        for name, rows in record["layers"].items():
            assert rows.shape[0] == len(at), name
        for at_block in at[kept:].reshape(-1, BLOCK):
            passes = at_block[at_block < STEPS]
            # pass s unmasks someone for every s up to the block's last
            assert set(passes) == set(range(passes.max() + 1))
            if rule != "dynamic_above_it":
                assert len(passes) == len(set(passes))   # one a pass
    # the counters: every block is closed by a pass that denoises the
    # next one and no pass does nothing but commit; behind a request's
    # last block its closing pass opens one more and an overshoot pass
    # (the one in flight when the last block landed) may denoise it
    assert stats["block_commit_passes"] == 0
    if rule == "dynamic_above_it":
        # the overshoot pass may find the block behind the last
        # unmasked whole, and close it
        assert blocks <= stats["block_commits_fused"] \
            <= blocks + len(records)
    else:
        assert stats["block_commits_fused"] == blocks
    assert generated <= stats["block_positions_unmasked"] \
        <= generated + 2 * BLOCK * len(records)
    # the tokens served, not what was dropped behind a request's last
    assert stats["block_tokens_landed"] == \
        sum(map(len, done.values())) < generated
    if rule == "dynamic_above_it":
        # wide logits: confidences pass 0.9 and passes unmask several
        # (a request's last closing pass and its overshoot set apart)
        assert stats["block_denoise_passes"] - 2 * len(records) \
            < 0.5 * generated
    else:
        # a pass a position, the closing pass behind a request's last
        # block and an overshoot pass at most: nothing for the commits
        assert generated + len(records) <= stats["block_denoise_passes"] \
            <= generated + 2 * len(records)


def test_engine_logits_agree_with_the_reference(served):
    """Each pass of slot 0's requests, as the engine's own model
    computes it from the engine's own cache (what the step program
    runs), against sdar_plain's noisy copy of the same block and pass
    at every position: of a plain pass the open block's, of a closing
    pass the finished block's clean copy and, where the record has the
    block behind it, that block's first pass; float32 on both sides,
    so the tolerance is the order of the sums alone (a paged gather
    against one masked softmax over the extended sequence), 2e-4 of
    the logits' scale."""
    _rule, _model, dims, params, _engine_, prompts, _done, records, \
        passes = served
    compared = closing = 0
    for request_id, record in records.items():
        mine = [p for p in passes if p[0] == request_id]
        if not mine:
            continue
        prompt = prompts[int(request_id[1:])]
        start = record["start"]
        at = record["layers"]["unmask"][start - record["first"]:, 0]
        clean = np.concatenate(
            [np.asarray(prompt[:start], np.int32), record["tokens"]])
        hidden, _ = plain.stack_hidden(
            params, *plain.extended(clean, start, at, STEPS, MASK),
            layers=dims["published_layers"], block=BLOCK,
            q_heads=dims["n_heads"], kv_heads=dims["n_kv_heads"],
            theta=dims["theta"], top_k=dims["top_k"], eps=dims["eps"])
        for _id, first, masked, _block, logits in mine:
            if first + BLOCK > len(clean):
                continue            # the overshoot pass behind the end
            mine_at = at[first - start:first - start + BLOCK]
            if not masked.any():
                # a closing pass: the finished block, then the next
                # one's first pass, all masked
                halves = [(first, plain.CLEAN, logits[:BLOCK])]
                if first + 2 * BLOCK <= len(clean):
                    halves.append((first + BLOCK, 0, logits[BLOCK:]))
                    closing += 1
            else:
                # the pass whose mask this is: the record's own
                copy = int(mine_at[masked].min())
                assert (masked == ((mine_at >= copy)
                                   & (mine_at < STEPS))).all()
                halves = [(first, copy, logits[:BLOCK])]
            for at_first, copy, got in halves:
                rows = [plain.extended_row(at_first + i, copy, len(clean),
                                           start) for i in range(BLOCK)]
                want = np.asarray(plain.head_logits(
                    hidden[np.asarray(rows)], params["final_norm"],
                    params["lm_head"]["kernel"], dims["eps"]))
                scale = np.abs(want).max()
                np.testing.assert_allclose(got, want, atol=2e-4 * scale)
                compared += 1
    assert compared >= 10 and closing >= 2


def test_the_reference_judges_every_token_and_choice_as_its_own(served):
    """request_readings, the judgement the cell's check is made of: in
    float32 every served token is the reference's best in the pass
    that unmasked it (gap 0), every routed choice and every unmask
    choice the reference's own (slack 0 but for sums' order)."""
    _rule, model, dims, params, _engine_, prompts, done, records, _ = \
        served
    for i, prompt in enumerate(prompts):
        read = MODULE.request_readings(
            params, prompt, done[f"r{i}"], records[f"r{i}"], model, dims)
        assert len(read["gaps"]) == NEW[i]
        assert max(read["gaps"]) < 1e-4
        assert all(max(slack) < 1e-4 for slack in read["slack"].values())
        assert read["unmask_slack"] and max(read["unmask_slack"]) < 1e-4
        assert set(read["slack"]) == {
            name for name, _k, _n in MODULE.decision_layers(model, dims)
            if name != MODULE.UNMASK}


def test_a_record_that_does_not_hold_the_served_tokens_is_refused(served):
    _rule, model, dims, params, _e, prompts, done, records, _ = served
    record = records["r0"]
    assert MODULE.request_readings(
        params, prompts[0], done["r0"], None, model, dims) is None
    wrong = list(done["r0"])
    wrong[2] = (wrong[2] + 1) % 256
    assert MODULE.request_readings(
        params, prompts[0], wrong, record, model, dims) is None
    short = {**record, "layers": {k: v[:-1] for k, v in
                                  record["layers"].items()}}
    assert MODULE.request_readings(
        params, prompts[0], done["r0"], short, model, dims) is None


def test_the_schedule_costs_a_block_its_denoise_passes():
    """One request of three blocks under the static rule (a position a
    pass): a block costs its ``steps`` passes and no pass of its own
    for the commit, which rides the next block's first; behind the
    last block come its closing pass and, dispatched before that
    landed, one overshoot pass."""
    _model, _dims, params, config = _built()
    engine = _engine(config, params, num_slots=1)
    engine.submit(Request("s", _prompts((8,), seed=8)[0], 3 * BLOCK))
    assert len(_drain(engine)["s"]) == 3 * BLOCK
    stats = engine.step_stats()
    assert engine.decode_steps == 3 * STEPS + 2
    assert stats["block_denoise_passes"] == 3 * STEPS + 2
    assert stats["block_commit_passes"] == 0
    assert stats["block_commits_fused"] == 3
    assert stats["block_tokens_landed"] == 3 * BLOCK
    commits = [launch.commits for launch in engine._ring
               if launch.kind == "decode"]
    assert commits == [0] * STEPS + ([1] + [0] * (STEPS - 1)) * 2 + [1, 0]


def test_a_first_block_of_given_tokens_is_closed_by_the_next_pass():
    """A prompt that leaves one position of its last block open: the
    pass behind the prefill (plain: the seated block has a mask) fills
    it, the very next one closes the block and opens the second, and
    the reference judges every token its own."""
    model, dims, params, config = _built()
    prompt = _prompts((7,), seed=9)[0]              # 3 given, 1 masked
    engine = _engine(config, params, num_slots=1)
    engine.submit(Request("g", prompt, 5))
    done = _drain(engine)["g"]
    commits = [launch.commits for launch in engine._ring
               if launch.kind == "decode"]
    assert commits[:2] == [0, 1]
    record = engine.take_decisions("g")
    assert record["layers"]["unmask"][4:8, 0].tolist() == [STEPS] * 3 + [0]
    read = MODULE.request_readings(params, prompt, done, record, model,
                                   dims)
    assert len(read["gaps"]) == 5 and max(read["gaps"]) < 1e-4
    assert max(read["unmask_slack"]) < 1e-4


def test_two_slots_at_different_passes_in_one_step():
    """The second request joins while the first is in mid-block: one
    launch then holds a closing slot (two live blocks) beside a plain
    one (a dead second half), and both requests read what they read
    alone."""
    _model, _dims, params, config = _built()
    prompts = _prompts((9, 6), seed=4)
    alone = {}
    for i, prompt in enumerate(prompts):
        engine = _engine(config, params)
        engine.submit(Request(f"a{i}", prompt, 10))
        alone[i] = _drain(engine)[f"a{i}"]
    engine = _engine(config, params)
    engine.submit(Request("b0", prompts[0], 10))
    for _ in range(3):
        engine.step()
    engine.submit(Request("b1", prompts[1], 10))
    done = _drain(engine)
    assert done["b0"] == alone[0] and done["b1"] == alone[1]
    mixed = [launch for launch in engine._ring
             if launch.kind == "decode" and launch.rows == 2
             and launch.commits == 1]
    assert mixed, "no step held a closing slot beside a plain one"


def test_a_slot_preempted_in_mid_block_generates_its_block_again():
    """A pool far under the aggregate worst case (overcommit): victims
    are evicted between two passes of a block, re-queued with the
    tokens they were served, and prefilled again over prompt +
    served; nothing served changes."""
    _model, _dims, params, config = _built()
    prompts = _prompts((6, 7, 5, 6), seed=5)
    want = {}
    for i, prompt in enumerate(prompts):
        engine = _engine(config, params)
        engine.submit(Request(f"p{i}", prompt, 18))
        want.update(_drain(engine))
    engine = _engine(config, params, num_slots=2, max_decode_len=32,
                     kv_page_size=8, kv_num_pages=5, overcommit=True)
    for i, prompt in enumerate(prompts):
        engine.submit(Request(f"p{i}", prompt, 18))
    done = _drain(engine)
    assert engine.preemptions > 0, "the scenario did not preempt"
    assert done == want
    engine.pages.check()


def test_an_eos_inside_a_block_ends_the_request_there():
    _model, _dims, params, config = _built()
    prompt = _prompts((10,), seed=6)[0]
    engine = _engine(config, params)
    engine.submit(Request("e", prompt, 12))
    whole = _drain(engine)["e"]
    # an eos that first shows in mid-block (not at a block's end)
    at = next(i for i, token in enumerate(whole)
              if whole.index(token) == i and (len(prompt) + i) % BLOCK
              not in (BLOCK - 1,))
    engine = _engine(config, params)
    engine.submit(Request("e", prompt, 12, eos_id=whole[at]))
    assert _drain(engine)["e"] == whole[:at + 1]
    assert engine.overshoot_tokens > 0


def test_a_reply_streams_a_block_at_a_time():
    _model, _dims, params, config = _built()
    engine = _engine(config, params)
    batches = []
    engine.on_tokens = batches.append
    prompt = _prompts((6,), seed=7)[0]       # 2 given, 2 served first
    engine.submit(Request("s", prompt, 9))
    done = _drain(engine)["s"]
    assert [len(batch) for batch in batches] == [2, 4, 3]
    assert [token for batch in batches for _id, token, _i in batch] == done
    assert [index for batch in batches for _id, _t, index in batch] == \
        list(range(9))


@pytest.mark.parametrize("fault,message", [
    (dict(kv_page_size=None, kv_num_pages=None), "paged"),
    (dict(kv_page_size=6, kv_num_pages=40, max_decode_len=126), "paged"),
    (dict(sampling="hot"), "greedy"),
])
def test_what_a_block_engine_refuses(fault, message):
    from batch_shipyard_tpu.models import inference as inf
    _model, _dims, params, config = _built()
    if fault.get("sampling"):
        fault["sampling"] = inf.SamplingConfig(temperature=0.7)
    with pytest.raises((ValueError, NotImplementedError), match=message):
        _engine(config, params, **fault)


def test_the_names_on_the_record_are_the_references():
    assert serving.UNMASK_NAME == plain.UNMASK
    assert serving.pass_layer_name("layer_3", 2) == \
        plain.pass_name("layer_3", 2)


def test_unmasking_by_confidence():
    """_unmasked_by alone: the least most confident of the masked, ties
    to the lowest index; under the dynamic rule everything above the
    threshold where that is at least as many."""
    static = tfm.BlockDiffusion(block=4, steps=2)
    dynamic = tfm.BlockDiffusion(block=4, steps=4,
                                 remask="low_confidence_dynamic",
                                 threshold=0.5)
    confidence = jnp.log(jnp.asarray([
        [0.2, 0.9, 0.9, 0.1], [0.6, 0.7, 0.1, 0.8],
        [0.3, 0.2, 0.1, 0.4], [0.9, 0.9, 0.9, 0.9]], jnp.float32))
    masked = jnp.asarray([[1, 1, 1, 1], [1, 0, 1, 1], [1, 1, 1, 0],
                          [0, 0, 0, 1]], bool)
    assert np.asarray(serving._unmasked_by(
        confidence, masked, static)).tolist() == [
            [False, True, True, False], [True, False, False, True],
            [True, True, False, False], [False, False, False, True]]
    assert np.asarray(serving._unmasked_by(
        confidence, masked, dynamic)).tolist() == [
            [False, True, True, False], [True, False, False, True],
            [True, False, False, False], [False, False, False, True]]
    tie = jnp.zeros((1, 4), jnp.float32) - 1.0
    assert np.asarray(serving._unmasked_by(
        tie, jnp.ones((1, 4), bool), dynamic)).tolist() == [
            [True, False, False, False]]
