"""The benchmark's family seam in tier-1: the cases of
benchmarks/tests/test_families.py (the seam) and test_mixtral_family.py
(the rehearsal family), two seconds together. Every other family's
module has a tier-1 file of its own (tests/test_benchmark_family_*.py:
``--dist loadfile`` keeps a file on one worker, and a star-import of two
modules keeps only the LAST function of a name), and the cases below
hold that arrangement: every family module has an importer, no importer
shadows a case. Beside them, the table of what each PR appended to
BENCHMARK.json (tests/benchmark_as_of.py) and PR 36's pin, which reads
it. `python -m pytest benchmarks/tests` still runs the cases where they
live."""
import ast
import pathlib

import pytest

_FILES = ("benchmarks.tests.test_families",
          "benchmarks.tests.test_mixtral_family")
pytest.register_assert_rewrite(*_FILES)

from benchmarks.tests.test_families import *          # noqa: E402,F401,F403
from benchmarks.tests.test_mixtral_family import *    # noqa: E402,F401,F403

from benchmark_as_of import APPENDED, as_of, row, undo    # noqa: E402

# Recorded without tier-1's low-optimisation XLA flags (tests/conftest.py),
# under which the CPU draws a normal's last bits differently: the digests
# are checked where they were recorded, by `python -m pytest
# benchmarks/tests`.
del test_seeded_weights_are_the_parents_bit_for_bit    # noqa: F821


# ------------------------------------- one tier-1 file a family's module

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _test_names(path):
    return [n.name for n in ast.parse(path.read_text()).body
            if isinstance(n, ast.FunctionDef) and n.name.startswith("test_")]


def _importers():
    """{tier-1 file: the modules of benchmarks/tests it star-imports}."""
    found = {}
    for path in sorted((_ROOT / "tests").glob("test_benchmark_famil*.py")):
        found[path.name] = [
            node.module.rsplit(".", 1)[1]
            for node in ast.parse(path.read_text()).body
            if isinstance(node, ast.ImportFrom)
            and (node.module or "").startswith("benchmarks.tests.")
            and node.names[0].name == "*"]
    return found


def test_every_family_module_has_a_tier1_importer():
    on_disk = {p.stem for p in (_ROOT / "benchmarks" / "tests").glob(
        "test_*_family.py")} | {"test_families"}
    imported = [m for mods in _importers().values() for m in mods]
    assert sorted(imported) == sorted(on_disk)


def test_no_importer_shadows_a_case():
    """Two modules star-imported into one file keep the LAST function
    of a name: thirteen cases of six names ran nowhere in tier-1 until
    PR 58 gave each family its file."""
    for name, mods in _importers().items():
        names = [n for m in mods for n in _test_names(
            _ROOT / "benchmarks" / "tests" / f"{m}.py")]
        assert len(names) == len(set(names)), (name, sorted(
            n for n in set(names) if names.count(n) > 1))


# ----------------------------- BENCHMARK.json as an earlier PR left it

def test_the_table_ends_at_the_file():
    from benchmarks import common
    bench = common.load_benchmark()
    assert as_of(APPENDED[-1].pr) == bench
    readers = tuple(n for r in APPENDED for n in r.readers)
    assert tuple(m["name"] for m in bench["per_layer"][-len(readers):]) \
        == readers


@pytest.mark.parametrize("pr", [r.pr for r in APPENDED])
def test_as_of_holds_nothing_of_a_later_row(pr):
    bench = as_of(pr)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {w for section in ("end_to_end", "per_layer")
              for m in bench[section] for w in m.get("workloads", ())}
    assert listed <= set(cells)
    for r in APPENDED:
        held = r.pr <= pr
        if r.config is not None:
            assert (r.config in [c["name"] for c in bench["configs"]]) \
                is held
            assert (r.cell in cells) is held
        names = [m["name"] for m in bench["per_layer"]]
        assert all((n in names) is held for n in r.readers)


def test_undoing_a_row_that_is_not_the_tail_raises():
    from benchmarks import common
    bench = common.load_benchmark()
    for r in APPENDED[:-1]:
        with pytest.raises(AssertionError):
            undo(bench, r)
    assert undo(bench, APPENDED[-1]) == as_of(APPENDED[-2].pr)


# ------------------------------------------- PR 36's four dispatch readers

@pytest.mark.parametrize("name", row(36).readers)
def test_dispatch_readers_are_appended_to_the_benchmark(name):
    from benchmarks import common
    bench = common.load_benchmark()
    assert tuple(m["name"] for m in as_of(36)["per_layer"][-4:]) \
        == row(36).readers
    sat = [w["name"] for w in bench["workloads"]
           if w["traffic"].endswith("-sat")]
    m = common.find_named(bench["per_layer"], name, "metric")
    want = {"name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "serve_tokens_per_s", "workloads": sat}
    if name == "dispatch_prefill_share":
        want["unit"] = "%"
    if name.endswith(".open"):
        want.update(moves="itl_p50_ms",
                    workloads=["mistral7b-d16.chat-r80"])
    assert m == want
    # every listed cell reports the end-to-end metric it moves, and the
    # reader loads by its name
    moved = common.find_named(bench["end_to_end"], m["moves"], "metric")
    assert set(m["workloads"]) <= set(moved["workloads"])
    assert callable(common.load_metric_reader(name))
