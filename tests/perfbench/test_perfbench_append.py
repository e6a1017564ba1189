"""An append rehearsal: a FOURTH configuration, its cell and its entries
arrive at the end of ``BENCHMARK.json`` (in memory; the tiny
other-architecture fixture of ``other_arch.py`` stands for it), and every
test that finds the three cells that exist still finds them: by name,
never by position. What the repair of ``test_perfbench_glm5next.py``
loosened is position and nothing else: the predicate of the third cell
still fails on a swapped, a dropped or a foreign entry."""
import copy
import json
import os

import pytest

from tests.perfbench.test_perfbench_glm5next import CELL, PER_LAYER_15, assert_manifest_entries_of_the_cell
from tests.perfbench.test_perfbench_manifest import cells_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FOURTH = "other_closed"
# the end-to-end metrics a new cell may join: those that name their cells
JOINED = ("out_tok_s", "itl_p995_ms", "ttft_p50_ms")


def real():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def appended():
    """The real manifest with a fourth configuration, a fourth cell, three
    per-layer entries of that cell and its name at the end of the lists of
    the end-to-end metrics that keep one: what a ``model_config`` PR adds."""
    m = real()
    m["configs"].append({"name": "other-tiny", "source": "https://example.org/other-tiny/config.json",
                         "file": "perfbench/configs/other-tiny.json", "reduced": [],
                         "why": "the seam, not an architecture"})
    m["workloads"].append({"name": FOURTH, "config": "other-tiny", "traffic": "rehearsal_closed", "chips": 1,
                           "why": "the rehearsal's closed loop on a configuration of the test-tree adapter"})
    for name, moves in (("decode_rows_mean.other", "out_tok_s"), ("other_decode_step_bytes", "out_tok_s"),
                        ("tpot_chat_p50_ms.other", "itl_p995_ms")):
        m["per_layer"].append({"name": name, "unit": "rows", "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": moves, "workloads": [FOURTH]})
    for e in m["end_to_end"]:
        if e["name"] in JOINED:
            e["workloads"].append(FOURTH)
    return m


def index_of(manifest, name):
    return [e["name"] for e in manifest["per_layer"]].index(name)


def swap_two_of_the_fifteen(m):
    i, j = index_of(m, PER_LAYER_15[3]), index_of(m, PER_LAYER_15[11])
    m["per_layer"][i], m["per_layer"][j] = m["per_layer"][j], m["per_layer"][i]


def drop_one_of_the_fifteen(m):
    del m["per_layer"][index_of(m, PER_LAYER_15[7])]


def take_the_cell_out_of_out_tok_s(m):
    next(e for e in m["end_to_end"] if e["name"] == "out_tok_s")["workloads"].remove(CELL)


def put_a_foreign_entry_among_the_fifteen(m):
    foreign = next(e for e in m["per_layer"] if e["workloads"] != [CELL])
    m["per_layer"].insert(index_of(m, PER_LAYER_15[5]), copy.deepcopy(foreign))


def give_one_of_the_fifteen_to_another_cell(m):
    m["per_layer"][index_of(m, PER_LAYER_15[9])]["workloads"] = [FOURTH]


def name_the_cell_twice(m):
    m["workloads"].append(copy.deepcopy(next(w for w in m["workloads"] if w["name"] == CELL)))


BREAKS = [swap_two_of_the_fifteen, drop_one_of_the_fifteen, take_the_cell_out_of_out_tok_s,
          put_a_foreign_entry_among_the_fifteen, give_one_of_the_fifteen_to_another_cell, name_the_cell_twice]


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
def test_the_third_cells_entries_are_found_on_the_real_manifest_and_behind_a_fourth_cell(build):
    m = build()
    assert_manifest_entries_of_the_cell(m)
    if build is appended:  # the rehearsal did append: the third cell is last of nothing any more
        assert m["workloads"][-1]["name"] == m["per_layer"][-1]["workloads"][0] == FOURTH
        assert m["configs"][-1]["name"] == "other-tiny" and len(m["workloads"]) == len(real()["workloads"]) + 1
        assert all(e["workloads"][-1] == FOURTH for e in m["end_to_end"] if e["name"] in JOINED)


@pytest.mark.parametrize("cell", [w["name"] for w in real()["workloads"]])
def test_the_harness_finds_a_cell_that_exists_the_same_way_behind_a_fourth(cell):
    """The lookups of ``perfbench/run.py`` ``main`` (cell by ``name``, configuration
    by the cell's ``config``, a metric's cells by its ``workloads``) and
    ``test_perfbench_manifest.cells_of`` on both manifests."""
    found = []
    for m in (real(), appended()):
        w = next((x for x in m["workloads"] if x["name"] == cell), None)
        cfg = next(c for c in m["configs"] if c["name"] == w["config"])
        in_cell = [e["name"] for group in ("end_to_end", "per_layer") for e in m[group]
                   if "workloads" not in e or cell in e["workloads"]]
        listed = [e["name"] for group in ("end_to_end", "per_layer") for e in m[group] if cell in cells_of(e, m)]
        assert in_cell == listed and "setup_s" in in_cell
        found.append((w, cfg, in_cell))
    assert found[0] == found[1]
    # and the fourth cell reads its own entries and setup_s, nothing of the three
    m = appended()
    fourth = [e["name"] for group in ("end_to_end", "per_layer") for e in m[group] if FOURTH in cells_of(e, m)]
    assert set(fourth) == {e["name"] for e in m["end_to_end"] if e["name"] in JOINED} | {
        "setup_s", "decode_rows_mean.other", "other_decode_step_bytes", "tpot_chat_p50_ms.other"}


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
@pytest.mark.parametrize("break_it", BREAKS, ids=[f.__name__ for f in BREAKS])
def test_the_repair_loosened_position_and_nothing_else(break_it, build):
    m = build()
    break_it(m)
    with pytest.raises((AssertionError, ValueError)):
        assert_manifest_entries_of_the_cell(m)


def test_entries_of_the_third_cell_appended_after_its_fifteen_pass_wherever_they_stand():
    m = appended()
    later = {"name": "later_metric.glm53", "unit": "ms", "better": "lower", "source": "program_span",
             "layer": "scheduler", "moves": "out_tok_s", "workloads": [CELL]}
    m["per_layer"].append(later)                                          # behind the fourth cell's
    m["per_layer"].insert(index_of(m, PER_LAYER_15[-1]) + 1, dict(later, name="sooner_metric.glm53"))  # right behind the 15
    assert_manifest_entries_of_the_cell(m)
