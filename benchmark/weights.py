"""Weights made by the BENCHMARK from --seed: on the device, in one
jitted call, in the type they are served in. The program's
own initialisers are not used (serve.build_params runs model.init
eagerly in float32: 14 GB for the serve configuration), and the plain
reference is handed these same arrays, so neither side takes anything
the other has made.

This file names no architecture. The parameter tree is a list of
leaves, ``(path, shape, dtype rule, init rule)``, that the
configuration's model module gives (benchmark/models/<model_module>.py,
``param_leaves``), each leaf with its own rules from a small closed
set:

  dtype rule  "served" (the type the weights are served in) |
              "float32"
  init rule   "ones" | "zeros" | ("normal", fan_in): normal with
              standard deviation 1/sqrt(fan_in), drawn in float32

Leaves are numbered in the sorted order of their paths and leaf ``i``
draws from ``fold_in(key, i)`` whatever its rule, so that a leaf's
values depend on the seed and its place alone. Which rule each leaf of
a model gets is listed under ``assumed`` in its configuration file."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DTYPE_RULES = ("served", "float32")


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (the
    driver's seeds are large; PRNGKey itself takes 32 signed bits)."""
    seed = int(seed)
    low, high = seed & 0x7FFFFFFF, seed >> 31
    return jax.random.fold_in(jax.random.PRNGKey(low), high)


def _sorted(leaves: list) -> list:
    """The leaves in the order they are numbered in, each rule checked
    against the closed set."""
    leaves = sorted(leaves, key=lambda leaf: leaf[0])
    for path, _shape, dtype_rule, init in leaves:
        normal = (isinstance(init, (tuple, list)) and len(init) == 2
                  and init[0] == "normal" and init[1] > 0)
        if dtype_rule not in DTYPE_RULES or not (
                init in ("ones", "zeros") or normal):
            raise ValueError(f"leaf {'/'.join(path)}: no rule "
                             f"{dtype_rule!r}, {init!r}")
    if len({leaf[0] for leaf in leaves}) != len(leaves):
        raise ValueError("a leaf path is listed twice")
    return leaves


def _dtype(rule: str, served):
    return jnp.float32 if rule == "float32" else served


def _unflatten(pairs) -> dict:
    tree: dict = {}
    for path, value in pairs:
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


def abstract_params(leaves: list, dtype) -> dict:
    return _unflatten(
        (path, jax.ShapeDtypeStruct(tuple(shape),
                                    _dtype(dtype_rule, dtype)))
        for path, shape, dtype_rule, _init in _sorted(leaves))


def make_params_fn(leaves: list, dtype):
    """key -> the whole parameter tree (to be called under jit)."""
    leaves = _sorted(leaves)

    def build(key):
        pairs = []
        for i, (path, shape, dtype_rule, init) in enumerate(leaves):
            leaf_dtype = _dtype(dtype_rule, dtype)
            if init == "ones":
                value = jnp.ones(shape, leaf_dtype)
            elif init == "zeros":
                value = jnp.zeros(shape, leaf_dtype)
            else:
                value = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * (1.0 / math.sqrt(init[1]))).astype(leaf_dtype)
            pairs.append((path, value))
        return _unflatten(pairs)

    return build


def make_params(leaves: list, seed: int, dtype) -> dict:
    """The whole parameter tree in one compiled call."""
    return jax.jit(make_params_fn(leaves, dtype))(seed_key(seed))
