"""The parameter tree and the parameter count of EVERY configuration of
BENCHMARK.json against the program's own model.init, each through its
model module at its file's ``rehearse_tiny`` sizes (a configuration a
later PR adds is a case with no edit here); the weights' rules on a
made-up tree of another shape, and a digest that pins the seeded
weights; the kernel's operations-and-bytes function against hand
counts."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, harness, peaks, spec, weights
from benchmark.drivers import serve

BENCH = spec.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]


def _kernel(name):
    return spec.load_module(spec.ROOT, BENCH, f"kernels/{name}.py")


def _tiny(config_name):
    """(module, the file at its rehearse_tiny sizes, dims, leaves)."""
    model = harness.merged(spec.load_config(config_name), True)
    module = spec.load_model(model)
    dims = module.dims(model)
    return module, model, dims, module.param_leaves(dims)


def _flat(tree):
    """{path as a tuple of names: leaf}, as param_leaves writes paths."""
    return {tuple(key.key for key in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=CONFIGS)
def program_tree(request):
    """The program's own tree: the engine built as a run builds it
    (the module's program_model through workloads/serve), and that
    engine's model initialised abstractly."""
    module, model, _dims, leaves = _tiny(request.param)
    engine = serve.build_engine(
        module, model, weights.make_params(leaves, 0, jnp.bfloat16))
    tree = jax.eval_shape(lambda: engine.model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((engine.num_slots, 1), jnp.int32),
        positions=jnp.zeros((1,), jnp.int32)))["params"]
    return leaves, tree


def test_weight_tree_is_the_programs_tree(program_tree):
    leaves, tree = program_tree
    theirs = {k: (leaf.shape, leaf.dtype)
              for k, leaf in _flat(tree).items()}
    mine = {k: (leaf.shape, leaf.dtype) for k, leaf in
            _flat(weights.abstract_params(leaves, jnp.bfloat16)).items()}
    assert mine == theirs


def test_parameter_count_matches_model_init(program_tree):
    leaves, tree = program_tree
    counted = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    assert flops.param_count(leaves) == counted


def test_published_sizes_give_the_issues_arithmetic():
    config = spec.load_config("baichuan-7b-serve-1chip")
    module = spec.load_model(config)
    dims = module.dims(config)
    assert dims["d_head"] == 128 and dims["n_layers"] == 16
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096
    count = flops.param_count(module.param_leaves(dims))
    assert count == 16 * per_layer + 64000 * 4096 + 4096
    assert round(count / 1e9, 2) == 3.50


@pytest.mark.parametrize("config_name", CONFIGS)
def test_seeded_weights_are_reproducible_and_typed(config_name):
    """Every leaf of every configuration: the type its rule states,
    the same arrays from the same seed, other arrays from another, and
    the standard deviation its rule states."""
    _module, _model, _dims, leaves = _tiny(config_name)
    a = _flat(weights.make_params(leaves, 2**31 + 99, jnp.bfloat16))
    b = _flat(weights.make_params(leaves, 2**31 + 99, jnp.bfloat16))
    c = _flat(weights.make_params(leaves, 98, jnp.bfloat16))
    rules = {path: (dtype_rule, init)
             for path, _shape, dtype_rule, init in leaves}
    assert set(rules) == set(a)
    normals = 0
    for key, (dtype_rule, init) in rules.items():
        assert a[key].dtype == (jnp.float32 if dtype_rule == "float32"
                                else jnp.bfloat16), key
        assert (a[key] == b[key]).all(), key
        values = np.asarray(a[key].astype(jnp.float32))
        if init == "ones":
            assert (values == 1).all(), key
        elif init == "zeros":
            assert (values == 0).all(), key
        else:
            normals += 1
            assert not (a[key] == c[key]).all(), key
            want = 1.0 / math.sqrt(init[1])
            assert 0.9 * want < values.std() < 1.1 * want, key
    assert normals


def test_weights_of_a_made_up_tree_of_another_shape():
    """What no configuration here has yet: a three-dimensional
    [E, d, f] leaf with fan-in d, a float32 zeros leaf (a routing
    bias), a float32 ones leaf, in the served type and not."""
    leaves = [
        (("layer_0", "experts", "up"), (4, 32, 48), "served",
         ("normal", 32)),
        (("layer_0", "router", "bias"), (4,), "float32", "zeros"),
        (("layer_0", "norm", "scale"), (32,), "float32", "ones"),
        (("layer_0", "conv", "kernel"), (3, 32), "float32",
         ("normal", 3)),
        (("layer_0", "pad"), (2, 2), "served", "zeros"),
    ]
    a = weights.make_params(leaves, 2**31 + 5, jnp.bfloat16)
    b = weights.make_params(list(reversed(leaves)), 2**31 + 5,
                            jnp.bfloat16)      # listed in any order
    layer = a["layer_0"]
    assert layer["experts"]["up"].shape == (4, 32, 48)
    assert layer["experts"]["up"].dtype == jnp.bfloat16
    assert layer["router"]["bias"].dtype == jnp.float32
    assert (layer["router"]["bias"] == 0).all()
    assert layer["norm"]["scale"].dtype == jnp.float32
    assert (layer["norm"]["scale"] == 1).all()
    assert layer["conv"]["kernel"].dtype == jnp.float32
    assert layer["pad"].dtype == jnp.bfloat16
    assert (layer["pad"] == 0).all()
    std = float(jnp.std(layer["experts"]["up"].astype(jnp.float32)))
    assert 0.95 / math.sqrt(32) < std < 1.05 / math.sqrt(32)
    for (_, x), (_, y) in zip(sorted(_flat(a).items()),
                              sorted(_flat(b).items())):
        assert x.dtype == y.dtype and (x == y).all()
    assert flops.param_count(leaves) == 4 * 32 * 48 + 4 + 32 + 96 + 4
    abstract = weights.abstract_params(leaves, jnp.bfloat16)
    assert {k: (v.shape, v.dtype) for k, v in _flat(abstract).items()} \
        == {k: (v.shape, v.dtype) for k, v in _flat(a).items()}


@pytest.mark.parametrize("leaf", [
    (("w",), (2, 2), "served", "uniform"),
    (("w",), (2, 2), "bfloat16", "ones"),
    (("w",), (2, 2), "served", ("normal", 0)),
])
def test_a_leaf_rule_outside_the_closed_set_is_refused(leaf):
    with pytest.raises(ValueError):
        weights.make_params([leaf], 1, jnp.bfloat16)


def _digest(params) -> str:
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(str(leaf.dtype).encode())
        digest.update(str(leaf.shape).encode())
        digest.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("seed,expected", [
    (2**31 + 26,
     "05baf027c4321c9c19157d447664ed252c2ce7900d6f9510099559cc72022889"),
    (26,
     "ada18fcc99e4830777325120abc622928106e677a2bf5f57aee0e35fbe49a0e3"),
])
def test_the_same_seed_makes_the_weights_it_made_before(seed, expected):
    """Digests computed on the PARENT of PR 26 (flops.param_shapes +
    weights.make_params, before the tree moved into the model module)
    at the Baichuan file's rehearse_tiny sizes: the order of the
    leaves and their fold_in numbering are what could slip."""
    _module, _model, dims, leaves = _tiny("baichuan-7b-serve-1chip")
    assert dims == {"d_model": 128, "n_heads": 2, "d_head": 64,
                    "d_ff": 256, "n_layers": 2, "vocab": 512}
    assert _digest(weights.make_params(leaves, seed, jnp.bfloat16)) \
        == expected


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
