"""BENCHMARK.json's lists grow at their END: the driver's contract has
every later configuration, cell and per-layer metric appended behind
what is there, and reads one put before or between accepted entries as
a change to them. tests/benchmark/test_bench_blocks.py (PR 46, under
``paths``: no later PR may edit it) asserts in ONE case both what SDAR's
entries are and that they are the LAST of their lists, which stopped
being true with the first configuration appended behind them (PR 50).
tests/conftest.py expects that case to fail, strictly; so that nothing
else the case asserts is lost with it, its whole body runs here,
unedited, over the lists as they stood when SDAR's entries were the
last, and what came behind them is held to being additions."""

import copy
import importlib.util
import pathlib

from benchmark import spec

_BLOCKS = (pathlib.Path(__file__).parent / "benchmark"
           / "test_bench_blocks.py")


def _blocks():
    found = importlib.util.spec_from_file_location(
        "accepted_test_bench_blocks", _BLOCKS)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def _through(entries, last):
    """``entries`` up to and including the last one ``last`` holds
    of, and the ones behind it."""
    cut = max(i for i, entry in enumerate(entries) if last(entry)) + 1
    return entries[:cut], entries[cut:]


def test_sdars_entries_stand_as_it_appended_them(monkeypatch):
    blocks = _blocks()
    bench = copy.deepcopy(spec.load_benchmark())
    then = dict(bench)
    then["configs"], later_configs = _through(
        bench["configs"], lambda c: c["name"] == blocks.CONFIG)
    then["workloads"], later_cells = _through(
        bench["workloads"], lambda w: w["name"] == blocks.CELL)
    then["per_layer"], later_metrics = _through(
        bench["per_layer"], lambda m: m["name"].endswith(".sdar"))
    monkeypatch.setattr(blocks, "BENCH", then)
    # every assertion of the accepted case, "the last" read as the
    # last of what had been brought by then
    blocks.test_every_entry_it_brought_lists_its_cell_alone()
    # and what was appended behind them touches none of them
    assert all(c["name"] != blocks.CONFIG for c in later_configs)
    assert all(w["config"] != blocks.CONFIG for w in later_cells)
    for metric in later_metrics:
        assert blocks.CELL not in metric.get("workloads", [])
    for cell in later_cells:
        assert not any(m["name"].endswith(".sdar")
                       for m in spec.load_cell(cell["name"]).per_layer)


def test_the_case_fails_on_its_three_positions_alone():
    """The accepted case, as it stands, over the file as it stands:
    if it fails, it fails at one of its three ``[-1]`` / ``[-15:]``
    assertions and nowhere before them."""
    import traceback
    blocks = _blocks()
    try:
        blocks.test_every_entry_it_brought_lists_its_cell_alone()
    except AssertionError as failed:
        frame = traceback.extract_tb(failed.__traceback__)[-1]
        source = _BLOCKS.read_text().splitlines()
        marker = next(i for i, line in enumerate(source, 1)
                      if "appended at the end of their lists" in line)
        assert frame.lineno > marker, source[frame.lineno - 1]
