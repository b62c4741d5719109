"""benchmarks/tests/test_mellum2_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Mellum 2 family: the configuration against its published copy, the
program against the reference and the reference against its quadratic
form, the scored tail, byte counts by kind of layer, the six readers on
a hand-made joined trace, the cell on longdoc-sat as it stands, the
rehearsal cell at --trace 0 and 2.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_mellum2_family")

from benchmarks.tests.test_mellum2_family import *    # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 42's cell and the file's end as PR 42 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_longdoc_sat_as_it_stands = pinned(
    test_the_cell_and_longdoc_sat_as_it_stands, 42)    # noqa: F821
