"""A cell registers the module that holds its predicate with the append
rehearsal (``test_perfbench_append.py`` ``CELL_TESTS``: one line a cell,
in a file no ``model_config`` PR may edit) WITHOUT an edit there: a cell
added since that table was written names its module here, and the hook
below enters it once the test modules are collected. A ``benchmark`` PR
that makes the rehearsal find a cell's module by convention takes this
file away again (PERF.md section 7)."""

# cell -> the module under tests/perfbench/ with its ``assert_manifest_entries_of_the_cell``
LATER_CELLS = {"doc_bytes_evabyte": "test_perfbench_evabyte"}


def pytest_collection_modifyitems(items):
    for module in {getattr(item, "module", None) for item in items}:
        if module is not None and module.__name__.rpartition(".")[2] == "test_perfbench_append":
            for cell, name in LATER_CELLS.items():
                module.CELL_TESTS.setdefault(cell, name)
