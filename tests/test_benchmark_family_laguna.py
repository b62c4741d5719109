"""benchmarks/tests/test_laguna_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Laguna family: the configuration against its published copy, the
program against the reference and the margin rule against the
reference's controls, byte counts by layer type, the ring copies by
opcode, the three new readers and the older ones on a hand-made joined
trace, the cell on gen-sat as it stands, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_laguna_family")

from benchmarks.tests.test_laguna_family import *    # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 53's cell and the file's end as PR 53 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_gen_sat_as_it_stands = pinned(
    test_the_cell_and_gen_sat_as_it_stands, 53)    # noqa: F821
