"""benchmarks/tests/test_olmo_hybrid_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Olmo-Hybrid family: the configuration against its published copy,
the program against the reference and the margin rule against the
reference's controls, byte counts by kind of layer, the four new
readers and the older ones on a hand-made joined trace, the cell on
sample-sat as it stands, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_olmo_hybrid_family")

from benchmarks.tests.test_olmo_hybrid_family import *  # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 49's cell and the file's end as PR 49 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_sample_sat_as_it_stands = pinned(
    test_the_cell_and_sample_sat_as_it_stands, 49)    # noqa: F821
