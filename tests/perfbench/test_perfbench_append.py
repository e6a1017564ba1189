"""An append rehearsal: a NINTH configuration, its cell and its entries
arrive in ``BENCHMARK.json`` the way the next ``model_config`` PR brings
them (in memory; the tiny other-architecture fixture of ``other_arch.py``
stands for it): the cell's name appended to the ``workloads`` of the
generic per-layer entries (one entry a metric since PR 56) and of the
end-to-end metrics that keep a list, a handful of entries of its own, the
total far under the 128 a manifest may hold. Every test that finds a cell
that exists still finds it: by name and cell, never by position. What
that loosened is position and nothing else: a cell's predicate still fails
on a swapped, a dropped or a foreign entry."""
import copy
import importlib

import pytest

from tests.perfbench.manifest_entries import entries_of, real
from tests.perfbench.test_perfbench_glm5next import CELL, PER_LAYER_15, assert_manifest_entries_of_the_cell
from tests.perfbench.test_perfbench_manifest import cells_of

NINTH = "other_closed"
# the end-to-end metrics a new cell may join: those that name their cells
JOINED = ("out_tok_s", "itl_p995_ms", "ttft_p50_ms")
# the generic per-layer metrics every cell reads: the ninth joins them by its name at the end of their lists
GENERIC = ("decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share", "stream_backlog_tokens_mean",
           "extend_dispatch_dev_ms", "decode_step_done_ms", "extend_wide_done_ms", "extend_device_share",
           "device_starved_share", "device_hold_max_ms", "gap_tail_extend_share")
# and its own, each with a file of its own: an adapter's readers and the counts of its mechanism
OWN = (("decode_step_roofline_share.other", "%", "out_tok_s"), ("other_read_busy_share", "%", "out_tok_s"),
       ("other_read_roofline_share", "%", "out_tok_s"), ("other_rows_read_mean", "rows", "out_tok_s"),
       ("other_summaries_read_mean", "rows", "out_tok_s"))
# the test module that holds each cell's predicate
CELL_TESTS = {
    "chat_decode_7b": "test_perfbench_manifest", "reason_decode_phi4flash": "test_perfbench_phi4flash",
    "doc_reason_glm53flash": "test_perfbench_glm5next", "doc_reason_gigachat35": "test_perfbench_gigachat35",
    "doc_reason_trinitymini": "test_perfbench_afmoe", "chat_sessions_solaropen2": "test_perfbench_solaropen2",
    "agent_sessions_kimik25": "test_perfbench_kimik2", "agent_files_minimaxm3": "test_perfbench_minimaxm3",
}


def appended():
    """The real manifest with a ninth configuration and cell: what a
    ``model_config`` PR adds, and nothing it may not (no entry edited but
    for the cell's name at the END of a list)."""
    m = real()
    m["configs"].append({"name": "other-tiny", "source": "https://example.org/other-tiny/config.json",
                         "file": "perfbench/configs/other-tiny.json", "reduced": [],
                         "why": "the seam, not an architecture"})
    m["workloads"].append({"name": NINTH, "config": "other-tiny", "traffic": "rehearsal_closed", "chips": 1,
                           "why": "the rehearsal's closed loop on a configuration of the test-tree adapter"})
    for e in m["per_layer"]:
        if e["name"] in GENERIC:
            e["workloads"].append(NINTH)
    for name, unit, moves in OWN:
        m["per_layer"].append({"name": name, "unit": unit, "better": "higher", "source": "program_span",
                               "layer": "step programs", "moves": moves, "workloads": [NINTH]})
    for e in m["end_to_end"]:
        if e["name"] in JOINED:
            e["workloads"].append(NINTH)
    return m


def entry(manifest, name):
    return next(e for e in manifest["per_layer"] if e["name"] == name)


def swap_the_cells_of_one_of_the_fifteen_and_a_foreign_entry(m):
    mine, foreign = entry(m, "dsa_selected_share"), entry(m, "int8_matmul_busy_share")
    assert CELL in mine["workloads"] and CELL not in foreign["workloads"]
    mine["workloads"], foreign["workloads"] = foreign["workloads"], mine["workloads"]


def swap_what_two_of_the_fifteen_move(m):
    a, b = entry(m, PER_LAYER_15[3]), entry(m, PER_LAYER_15[11])
    assert a["moves"] != b["moves"]
    a["moves"], b["moves"] = b["moves"], a["moves"]


def drop_one_of_the_fifteen(m):
    m["per_layer"].remove(entry(m, PER_LAYER_15[7]))


def take_the_cell_out_of_out_tok_s(m):
    next(e for e in m["end_to_end"] if e["name"] == "out_tok_s")["workloads"].remove(CELL)


def put_a_foreign_entry_under_a_name_of_the_fifteen(m):
    foreign = copy.deepcopy(entry(m, "int8_matmul_busy_share"))
    m["per_layer"].append(dict(foreign, name=PER_LAYER_15[5], workloads=foreign["workloads"] + [CELL]))


def give_one_of_the_fifteen_to_another_cell(m):
    entry(m, PER_LAYER_15[10])["workloads"] = [m["workloads"][-1]["name"]]


def take_the_cell_out_of_a_merged_entry(m):
    entry(m, PER_LAYER_15[0])["workloads"].remove(CELL)


def leave_an_entry_without_its_list(m):
    del entry(m, PER_LAYER_15[1])["workloads"]


def name_the_cell_twice(m):
    m["workloads"].append(copy.deepcopy(next(w for w in m["workloads"] if w["name"] == CELL)))


BREAKS = [swap_the_cells_of_one_of_the_fifteen_and_a_foreign_entry, swap_what_two_of_the_fifteen_move,
          drop_one_of_the_fifteen, take_the_cell_out_of_out_tok_s, put_a_foreign_entry_under_a_name_of_the_fifteen,
          give_one_of_the_fifteen_to_another_cell, take_the_cell_out_of_a_merged_entry, leave_an_entry_without_its_list,
          name_the_cell_twice]


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
def test_the_third_cells_entries_are_found_on_the_real_manifest_and_behind_a_ninth_cell(build):
    m = build()
    assert_manifest_entries_of_the_cell(m)
    if build is appended:  # the rehearsal did append: the eighth cell is last of nothing any more
        assert m["workloads"][-1]["name"] == m["per_layer"][-1]["workloads"][0] == NINTH
        assert m["configs"][-1]["name"] == "other-tiny" and len(m["workloads"]) == len(real()["workloads"]) + 1
        assert all(e["workloads"][-1] == NINTH for e in m["end_to_end"] if e["name"] in JOINED)
        assert len(m["per_layer"]) == len(real()["per_layer"]) + len(OWN) <= 128  # the door is open
        assert set(entries_of(m, NINTH)) == set(GENERIC) | {name for name, _, _ in OWN}


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
@pytest.mark.parametrize("cell", sorted(CELL_TESTS))
def test_every_cells_own_test_finds_its_entries_behind_a_ninth_cell(cell, build):
    assert sorted(CELL_TESTS) == sorted(w["name"] for w in real()["workloads"])
    module = importlib.import_module("tests.perfbench." + CELL_TESTS[cell])
    module.assert_manifest_entries_of_the_cell(build())


@pytest.mark.parametrize("cell", [w["name"] for w in real()["workloads"]])
def test_the_harness_finds_a_cell_that_exists_the_same_way_behind_a_ninth(cell):
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
    # and the ninth cell reads the generic entries it joined, its own and setup_s, nothing else of the eight
    m = appended()
    ninth = [e["name"] for group in ("end_to_end", "per_layer") for e in m[group] if NINTH in cells_of(e, m)]
    assert set(ninth) == {e["name"] for e in m["end_to_end"] if e["name"] in JOINED} | {"setup_s"} | set(GENERIC) | {
        name for name, _, _ in OWN}


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
@pytest.mark.parametrize("break_it", BREAKS, ids=[f.__name__ for f in BREAKS])
def test_the_repair_loosened_position_and_nothing_else(break_it, build):
    m = build()
    break_it(m)
    with pytest.raises((AssertionError, ValueError, KeyError)):
        assert_manifest_entries_of_the_cell(m)


@pytest.mark.parametrize("build", [real, appended], ids=["real", "appended"])
def test_where_an_entry_stands_is_free(build):
    m = build()
    names = [e["name"] for e in m["per_layer"]]
    i, j = names.index(PER_LAYER_15[3]), names.index(PER_LAYER_15[11])
    m["per_layer"][i], m["per_layer"][j] = m["per_layer"][j], m["per_layer"][i]
    m["per_layer"].reverse()
    assert_manifest_entries_of_the_cell(m)


def test_entries_of_the_third_cell_appended_after_its_fifteen_pass_wherever_they_stand():
    m = appended()
    later = {"name": "later_metric.glm53", "unit": "ms", "better": "lower", "source": "program_span",
             "layer": "scheduler", "moves": "out_tok_s", "workloads": [CELL]}
    m["per_layer"].append(later)                                          # behind the ninth cell's
    names = [e["name"] for e in m["per_layer"]]
    m["per_layer"].insert(names.index(PER_LAYER_15[-1]) + 1, dict(later, name="sooner_metric.glm53"))  # among the 15
    assert_manifest_entries_of_the_cell(m)
