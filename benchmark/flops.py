"""Parameter count of a configuration, from the leaf list its model
module gives (benchmark/models/<model_module>.py, ``param_leaves``):
the same list the weights are made from, so the count and the tree
cannot drift apart. No architecture is named here; the dense block's
tree is in benchmark/models/dense_mha.py and is checked against
model.init in tests/benchmark."""

from __future__ import annotations

import math


def param_count(leaves: list) -> int:
    return sum(math.prod(shape) for _path, shape, *_rules in leaves)
