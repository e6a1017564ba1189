"""A cell's per-layer entries, found by NAME and CELL: the one lookup the
per-cell tests share. An entry belongs to a cell when the cell stands in
its ``workloads`` list; where it stands in the manifest, what other cells
the list holds and which entry comes last are nobody's to pin, so the
next cell joins a metric by appending its name to the list."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
JOINED_END_TO_END = ("out_tok_s", "itl_p995_ms")
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}
# the entries that move the tail; every other entry a cell's test names moves ``out_tok_s``. One entry a metric
# has one ``moves`` for all its cells, so what each had is stated once, here
MOVES_ITL = frozenset({
    "tpot_chat_p50_ms", "extend_dispatch_dev_ms", "extend_wide_done_ms", "extend_narrow_done_ms", "device_hold_max_ms",
    "gap_tail_extend_share", "itl_p99_ms", "prefill_cross_skipped_share"})


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def real():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def entries_of(manifest, cell):
    """``{name: entry}`` of the per-layer entries that list ``cell``. An
    entry WITHOUT a list is a fault (it would mean every cell, the next
    one too), so it raises ``KeyError`` here and not a pass anywhere."""
    return {e["name"]: e for e in manifest["per_layer"] if cell in e["workloads"]}


def metric_spec(name):
    """The reader file of a manifest name, as ``perfbench/run.py`` finds it."""
    from perfbench.run import layer_metric_file

    return load(layer_metric_file(name))


def assert_cell_holds(manifest, cell, names):
    """The cell is named once; its set of per-layer names CONTAINS
    ``names``; each moves ``itl_p995_ms`` if in ``MOVES_ITL`` and
    ``out_tok_s`` otherwise, has just the contract's keys and a file the
    harness can read, and is a percentage where it is a share of a
    roofline; and the end-to-end metrics that keep a list hold the cell."""
    assert [w["name"] for w in manifest["workloads"]].count(cell) == 1
    mine = entries_of(manifest, cell)
    assert len(mine) == sum(1 for e in manifest["per_layer"] if cell in e["workloads"])  # no name twice
    missing = [n for n in names if n not in mine]
    assert not missing, f"{cell} lacks {missing}"
    for name in names:
        e = mine[name]
        assert set(e) == ENTRY_KEYS
        assert e["moves"] == ("itl_p995_ms" if name in MOVES_ITL else "out_tok_s"), name
        assert metric_spec(name)["reader"]
        if "roofline" in name:
            assert e["unit"] == "%" and name.split(".")[0].endswith("_roofline_share")
    for e in manifest["end_to_end"]:
        if e["name"] in JOINED_END_TO_END:
            assert cell in e["workloads"], e["name"]
    return mine
