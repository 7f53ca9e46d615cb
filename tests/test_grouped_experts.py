"""The routed experts' second road (moe.grouped_experts: pairs sorted
by expert, one grouped matmul a stack, ops/grouped_matmul.py) against
the first (moe.dense_experts) and against a float64 loop over rows and
experts; the rule that picks between them (moe.experts_road); and what
the benchmark's reader makes of a launch record that names the road.
CPU: the kernel runs in the Pallas interpreter (``interpret=True``);
whether the TPU's compiler takes it, and at what cost in memory, is
tests/test_tpu_lowering.py's."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import moe, serving
from batch_shipyard_tpu.ops import grouped_matmul as gm
from benchmark.layer_metrics.readers import launch_rows

D_MODEL, N_EXPERTS, TOP_K = 128, 16, 3
# what the held share is: all sixteen, or experts 4..11
SHARES = {"all_held": (16, 0), "share_from_4": (8, 4)}
# case -> (rows, d_expert, how the choices are drawn)
CASES = {
    "an_expert_nobody_chose": (40, 128, "none_on_first_held"),
    "every_pair_on_one_expert": (40, 128, "one_expert"),
    "a_row_with_no_held_choice": (40, 128, "rows_elsewhere"),
    # 37 x 3 = 111 pairs: not a multiple of the row tile (128)
    "pairs_not_a_multiple_of_the_row_tile": (37, 128, "random"),
    # as Nemotron's 1856 = 14.5 x 128
    "d_expert_not_a_multiple_of_128": (40, 232, "random"),
    "alone_and_among_255_others": (256, 128, "random"),
}


def _choices(how: str, rows: int, held: int, first: int, rng):
    """[rows, k] experts, distinct in a row but for "one_expert"."""
    if how == "one_expert":
        # all rows x k pairs on ONE held expert, one group as long as
        # the whole input (a router's top-k never repeats an expert
        # in a row; the layer's functions take what they are given)
        return np.full((rows, TOP_K), first + 1)
    allowed = list(range(N_EXPERTS))
    if how == "none_on_first_held":
        allowed.remove(first)
    chosen = np.stack([rng.choice(allowed, TOP_K, replace=False)
                       for _ in range(rows)])
    if how == "rows_elsewhere":
        # rows 3, 4 and the last choose nothing that is held (with
        # all sixteen held there is no such expert: then nothing
        # they choose has a weight)
        elsewhere = [e for e in range(N_EXPERTS)
                     if not first <= e < first + held]
        for row in (3, 4, rows - 1):
            if elsewhere:
                chosen[row] = rng.choice(elsewhere, TOP_K,
                                         replace=False)
    return chosen


def _loop(rows, chosen, weights, up, down, first, gate):
    """sum_i w_i Expert_i(row) over held choices, one row and one
    expert at a time, in float64 on the operands as given."""
    rows, up, down = (np.asarray(x, np.float64) for x in (rows, up,
                                                          down))
    gate = None if gate is None else np.asarray(gate, np.float64)
    out = np.zeros((rows.shape[0], down.shape[2]))
    for m, e in itertools.product(range(rows.shape[0]),
                                  range(chosen.shape[1])):
        local = int(chosen[m, e]) - first
        if not 0 <= local < up.shape[0]:
            continue
        hidden = rows[m] @ up[local]
        if gate is None:
            hidden = np.square(np.maximum(hidden, 0.0))
        else:
            g = rows[m] @ gate[local]
            hidden = g / (1.0 + np.exp(-g)) * hidden
        out[m] += float(weights[m, e]) * hidden @ down[local]
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("share", sorted(SHARES))
@pytest.mark.parametrize("gated", [False, True],
                         ids=["relu2", "gated"])
def test_the_grouped_road_gives_the_dense_roads_sum(gated, share, case,
                                                     dtype):
    held, first = SHARES[share]
    rows_n, d_expert, how = CASES[case]
    rng = np.random.default_rng(
        sorted(CASES).index(case) * 4 + gated * 2 + (first > 0))
    dtype = jnp.dtype(dtype)

    def normal(shape, scale):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    rows = normal((rows_n, D_MODEL), 1.0)
    up = normal((held, D_MODEL, d_expert), D_MODEL ** -0.5)
    down = normal((held, d_expert, D_MODEL), d_expert ** -0.5)
    gate = normal((held, D_MODEL, d_expert),
                  D_MODEL ** -0.5) if gated else None
    chosen = _choices(how, rows_n, held, first, rng)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, chosen.shape),
                          jnp.float32)
    here = (chosen >= first) & (chosen < first + held)
    if how == "one_expert":
        assert here.all()
    if how == "none_on_first_held":
        assert not (chosen == first).any()
    chosen = jnp.asarray(chosen, jnp.int32)
    args = (rows, chosen, weights, up, down, first, gate)

    dense = np.asarray(moe.dense_experts(*args))
    grouped = np.asarray(moe.grouped_experts(*args, interpret=True))
    loop = _loop(*args)
    assert grouped.dtype == np.float32
    assert grouped.shape == (rows_n, D_MODEL)
    assert np.isfinite(grouped).all()
    # a row with no held choice gets exactly nothing, not 0 x garbage
    assert not np.abs(grouped[~here.any(axis=1)]).any()
    if dtype == jnp.float32:
        np.testing.assert_allclose(grouped, dense, rtol=0, atol=1e-5)
        np.testing.assert_allclose(grouped, loop, rtol=0, atol=1e-5)
    else:
        # the same rounding points (bfloat16 operands, float32 sums,
        # one rounding of the weighed hidden row): as far from the
        # loop as the dense road is, and from the dense road by the
        # few hidden values that round the other way
        far = np.abs(dense - loop).max()
        assert np.abs(grouped - loop).max() <= 1.5 * far + 1e-6
        if how != "one_expert":
            # (there the dense road rounds a row's hidden vector once,
            # weighed by the SUM of its k weights on the one expert)
            assert np.abs(grouped - dense).max() <= far + 1e-6
    if case == "alone_and_among_255_others":
        # no capacity, nothing dropped: a row's output is a function
        # of that row alone
        for i in (0, 100, 255):
            alone = moe.grouped_experts(
                rows[i:i + 1], chosen[i:i + 1], weights[i:i + 1],
                up, down, first, gate, interpret=True)
            np.testing.assert_allclose(
                np.asarray(alone)[0], grouped[i], rtol=0,
                atol=2e-6 if dtype == jnp.float32 else 0.0)


def test_rows_behind_the_last_group_are_never_written():
    """The kernel's contract (ops/grouped_matmul.py): rows of no group
    keep what the buffer held; grouped_experts takes them out by a
    select. Poisoned here: NaN rows behind the groups leave the rows
    of the groups as they were."""
    rng = np.random.default_rng(5)
    lhs = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((3, 128, 128)), jnp.float32)
    sizes = jnp.asarray([50, 0, 41], jnp.int32)
    clean = np.asarray(gm.grouped_matmul(lhs, rhs, sizes,
                                         interpret=True))
    poisoned = np.asarray(gm.grouped_matmul(
        lhs.at[91:].set(jnp.nan), rhs, sizes, interpret=True))
    np.testing.assert_array_equal(poisoned[:91], clean[:91])
    want = np.concatenate([np.asarray(lhs[:50]) @ np.asarray(rhs[0]),
                           np.asarray(lhs[50:91]) @ np.asarray(rhs[2])])
    np.testing.assert_allclose(clean[:91], want, rtol=0, atol=1e-4)
    with pytest.raises(ValueError, match="multiple of 128"):
        gm.grouped_matmul(lhs[:100], rhs, sizes, interpret=True)


@pytest.mark.parametrize("k,n,tiles", [
    (2688, 1856, (128, 2688, 640)),      # Nemotron up: 3 x 640
    (1856, 2688, (128, 1856, 896)),      # Nemotron down: 3 x 896
    (4096, 1280, (128, 4096, 512)),      # Solar-Open2 up / gate
    (1280, 4096, (128, 1280, 1408)),     # Solar-Open2 down
    (128, 232, (128, 128, 256)),
])
def test_the_tiling_takes_all_of_k_and_lane_aligned_columns(k, n,
                                                            tiles):
    assert gm.tiling(k, n, 2) == tiles
    _tm, tk, tn = tiles
    assert tk * tn * 2 <= 4 << 20 and tn % 128 == 0


@pytest.mark.parametrize("backend,rows,road", [
    ("tpu", 96, "dense"),        # a decode step's slots
    ("tpu", 64, "dense"),        # the shortest prefill bucket
    ("tpu", 128, "dense"),
    ("tpu", 512, "grouped"),
    ("tpu", 1024, "grouped"),
    ("cpu", 96, "dense"),
    ("cpu", 512, "dense"),       # off the TPU there is no kernel
    ("cpu", 1024, "dense"),
])
def test_the_road_is_chosen_from_the_rows_alone(monkeypatch, backend,
                                                rows, road):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for config in (
            moe.RoutedConfig(d_model=2688, n_experts=128, top_k=6,
                             d_expert=1856, experts_held=64),
            moe.RoutedConfig(d_model=4096, n_experts=320, top_k=8,
                             d_expert=1280, experts_held=40,
                             gated=True)):
        assert moe.experts_road(rows, config) == road


def _row(landed):
    return {"mono_start": 0.0, "no_work_seconds": 0.0,
            "landed": landed}


@pytest.mark.parametrize("value,kind", [
    ("share_of_window", "prefill"), ("ms_per_ktoken", "prefill"),
    ("padding_pct", "prefill"), ("period_ms", "decode"),
    ("ready_pct", "all"), ("no_work_pct", "all")])
def test_the_launch_rows_reader_reads_a_record_with_a_road(value,
                                                           kind):
    """benchmark/layer_metrics/readers/launch_rows.py (the parent's,
    untouched) gives the same number for rows whose prefill entries
    carry the new ``road`` key as for rows without it."""
    launches = [
        serving.Launch("decode", 0.0, 0.02, 20.0, 0.0, False, 1,
                       rows=96),
        serving.Launch("prefill", 0.02, 0.05, 30.0, 1.0, False, 1,
                       path="recomputed", bucket=512, tokens=400,
                       request_id="a", road="grouped"),
        serving.Launch("prefill", 0.05, 0.06, 10.0, 1.0, True, 1,
                       path="cold", bucket=64, tokens=60,
                       request_id="b", road="dense")]
    entries = [launch.entry() for launch in launches]
    assert [e.get("road") for e in entries] == [None, "grouped",
                                                "dense"]
    without = [{k: v for k, v in e.items() if k != "road"}
               for e in entries]
    params = {"kind": kind, "value": value, "pct": 50}
    got = launch_rows.value([_row(entries)], 1.0, params)
    assert got is not None
    assert got == launch_rows.value([_row(without)], 1.0, params)
