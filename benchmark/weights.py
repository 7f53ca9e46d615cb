"""Weights made by the BENCHMARK from --seed: on the device, in one
jitted call, in the type they are served in. The program's
own initialisers are not used (serve.build_params runs model.init
eagerly in float32: 14 GB for the serve configuration), and the plain
reference is handed these same arrays, so neither side takes anything
the other has made.

Initialisation (listed under ``assumed`` in the configuration files):
normal, std 1/sqrt(fan_in) for every kernel and 1/sqrt(hidden) for the
embedding, ones for the RMSNorm scales (kept float32, as the model
declares them)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import flops


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (the
    driver's seeds are large; PRNGKey itself takes 32 signed bits)."""
    seed = int(seed)
    low, high = seed & 0x7FFFFFFF, seed >> 31
    return jax.random.fold_in(jax.random.PRNGKey(low), high)


def _leaves(dims: dict) -> list[tuple[tuple, tuple]]:
    out = []

    def walk(node, path):
        if isinstance(node, tuple):
            out.append((path, node))
        else:
            for name in sorted(node):
                walk(node[name], path + (name,))

    walk(flops.param_shapes(dims), ())
    return out


def _unflatten(pairs) -> dict:
    tree: dict = {}
    for path, value in pairs:
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


def abstract_params(dims: dict, dtype) -> dict:
    return _unflatten(
        (path, jax.ShapeDtypeStruct(
            shape, jnp.float32 if path[-1] == "scale" else dtype))
        for path, shape in _leaves(dims))


def make_params_fn(dims: dict, dtype):
    """key -> the whole parameter tree (to be called under jit)."""
    leaves = _leaves(dims)

    def build(key):
        pairs = []
        for i, (path, shape) in enumerate(leaves):
            if path[-1] == "scale":
                value = jnp.ones(shape, jnp.float32)
            else:
                fan_in = shape[1] if path[-1] == "embedding" \
                    else shape[0]
                value = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * (1.0 / math.sqrt(fan_in))).astype(dtype)
            pairs.append((path, value))
        return _unflatten(pairs)

    return build


def make_params(dims: dict, seed: int, dtype) -> dict:
    """The whole parameter tree in one compiled call."""
    return jax.jit(make_params_fn(dims, dtype))(seed_key(seed))
