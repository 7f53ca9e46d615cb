"""The parameter tree and the parameter count against model.init at a
tiny size; the kernel's operations-and-bytes function against hand
counts."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops, peaks, spec, weights

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "intermediate_size": 192, "num_hidden_layers": 3,
        "vocab_size": 320}


def _kernel(name):
    return spec.load_module(spec.ROOT, spec.load_benchmark(),
                            f"kernels/{name}.py")


@pytest.fixture(scope="module")
def program_tree():
    from batch_shipyard_tpu.models import transformer as tfm
    dims = flops.model_dims(TINY)
    config = tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        d_head=dims["d_head"], d_ff=dims["d_ff"], max_seq_len=32,
        param_dtype=jnp.bfloat16)
    return config, jax.eval_shape(
        lambda: tfm.TransformerLM(config).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[
                "params"]


def test_weight_tree_is_the_programs_tree(program_tree):
    _config, tree = program_tree
    dims = flops.model_dims(TINY)
    ours = weights.abstract_params(dims, jnp.bfloat16)
    theirs = {jax.tree_util.keystr(p): (leaf.shape, leaf.dtype)
              for p, leaf in
              jax.tree_util.tree_flatten_with_path(tree)[0]}
    mine = {jax.tree_util.keystr(p): (leaf.shape, leaf.dtype)
            for p, leaf in
            jax.tree_util.tree_flatten_with_path(ours)[0]}
    assert mine == theirs


def test_parameter_count_matches_model_init(program_tree):
    _config, tree = program_tree
    counted = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    assert flops.param_count(flops.model_dims(TINY)) == counted


def test_published_sizes_give_the_issues_arithmetic():
    serve = spec.load_cell("baichuan7b.chat-online").config
    dims = flops.model_dims(serve)
    assert dims["d_head"] == 128 and dims["n_layers"] == 16
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096
    assert flops.param_count(dims) == \
        16 * per_layer + 64000 * 4096 + 4096
    assert round(flops.param_count(dims) / 1e9, 2) == 3.50


def test_seeded_weights_are_reproducible_and_typed():
    dims = flops.model_dims(TINY)
    a = weights.make_params(dims, 2**31 + 99, jnp.bfloat16)
    b = weights.make_params(dims, 2**31 + 99, jnp.bfloat16)
    c = weights.make_params(dims, 98, jnp.bfloat16)
    ka = a["layer_0"]["attn"]["q_proj"]["kernel"]
    assert ka.dtype == jnp.bfloat16
    assert a["final_norm"]["scale"].dtype == jnp.float32
    assert (ka == b["layer_0"]["attn"]["q_proj"]["kernel"]).all()
    assert not (ka == c["layer_0"]["attn"]["q_proj"]["kernel"]).all()
    std = float(jnp.std(ka.astype(jnp.float32)))
    assert 0.8 / 8 < std < 1.2 / 8          # 1/sqrt(fan_in = 64)


def test_paged_decode_work_by_hand():
    work = _kernel("paged_decode").call_work(
        tokens=1000, slots=48, n_heads=32, d_head=128)
    assert work["bytes"] == 2 * 1000 * 4096 * 2 + 2 * 48 * 4096 * 2
    assert work["flops"] == 4 * 1000 * 4096
    # memory-bound on a v5e by a wide margin
    v5e = peaks.for_device_kind("TPU v5 lite")
    assert work["bytes"] / v5e["hbm_bytes_per_s"] > \
        10 * work["flops"] / v5e["bf16_flops_per_s"]


def test_paged_decode_work_over_a_traced_slice():
    module = _kernel("paged_decode")
    obs = {"dims": {"n_heads": 32, "d_head": 128},
           "counters": {"num_slots": 48},
           "traced_steps": [(0, 1, 2, 0, 0, 900), (1, 2, 2, 0, 0, 1100),
                            (2, 3, 0, 0, 0, 0)]}
    total = module.work(obs, {"decode": 32})
    one = module.call_work(1000, 48, 32, 128)
    assert total["bytes"] == one["bytes"] * 32
    assert module.work(obs, {"decode": 0}) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_device_kind("TPU v9 imaginary")
    assert peaks.for_device_kind("TPU v5 lite")["hbm_bytes"] == 16e9
