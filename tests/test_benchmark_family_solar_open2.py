"""benchmarks/tests/test_solar_open2_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Solar-Open2 family: the configuration against its published copy,
a chip's share against the reference, byte counts, three readers,
doc-sat, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_solar_open2_family")

from benchmarks.tests.test_solar_open2_family import *  # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 32's cell: its per-layer list as PR 34 left it
# (tests/benchmark_as_of.py)
test_the_cell_and_doc_sat = pinned(
    test_the_cell_and_doc_sat, 34)    # noqa: F821
