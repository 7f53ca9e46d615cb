"""Model module ``routed_standin`` (tests/benchmark only): a ROUTED
architecture as the harness meets it, before the program serves one.
Beside the four functions every model module has it gives
``decision_layers``: one name a layer, each choosing
``num_experts_per_tok`` of ``num_experts``, so benchmark/check.py runs
the reference on the timed path's own choices and judges each.

The harness finds it by name under ``paths`` (spec.load_model), here
under tests/benchmark/models/; the tests that show what a routed
configuration ADDS copy it to benchmark/models/ of a copy of the tree.
Its reference is loaded by path for the same reason: a module under
benchmark/models/ imports its reference from benchmark.reference."""

from __future__ import annotations

from benchmark import spec

plain = spec.load_module(spec.ROOT, spec.load_benchmark(),
                         "reference/routed_standin_plain.py")


def dims(config: dict) -> dict:
    return {"d_model": int(config["hidden_size"]),
            "d_expert": int(config["moe_intermediate_size"]),
            "n_experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "n_layers": int(config["num_hidden_layers"]),
            "vocab": int(config["vocab_size"])}


def decision_layers(config: dict, dims: dict) -> list:
    """Every layer routes: [(layer name, k, n)]."""
    return [(f"layer_{i}", dims["top_k"], dims["n_experts"])
            for i in range(dims["n_layers"])]


def param_leaves(dims: dict) -> list:
    d, f, e = dims["d_model"], dims["d_expert"], dims["n_experts"]
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]
    for i in range(dims["n_layers"]):
        layer = f"layer_{i}"
        out += [
            ((layer, "mix_norm", "scale"), (d,), "float32", "ones"),
            ((layer, "moe_norm", "scale"), (d,), "float32", "ones"),
            ((layer, "mix", "kernel"), (3, d), "served", ("normal", 3)),
            ((layer, "mix", "out"), (d, d), "served", ("normal", d)),
            ((layer, "router", "kernel"), (d, e), "served",
             ("normal", d)),
            # the routing bias: selection by score + bias, weights by
            # the score alone
            ((layer, "router", "bias"), (e,), "float32",
             ("normal", 100)),
            ((layer, "experts", "gate"), (e, d, f), "served",
             ("normal", d)),
            ((layer, "experts", "up"), (e, d, f), "served",
             ("normal", d)),
            ((layer, "experts", "down"), (e, f, d), "served",
             ("normal", f))]
    return out


def program_model(config: dict, dims: dict, engine: dict, **options):
    """What the stand-in's engine is built from: the sizes and the
    program's options (a control's overrides land here)."""
    return {"n_layers": dims["n_layers"], "top_k": dims["top_k"],
            "eps": float(config["norm_eps"]), **options}


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None):
    return plain.teacher_forced_logits(
        params, tokens, rows, n_layers=dims["n_layers"],
        top_k=dims["top_k"], eps=float(config["norm_eps"]),
        decisions=decisions)
