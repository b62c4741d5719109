"""benchmarks/tests/test_phi4flash_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Phi-4-mini-flash family: the configuration against its published
copy (``reduced`` the page table's width alone, every ``assumed`` item
named in the family file), the program against the reference and the
margin rule against the reference's controls, byte counts by kind of
layer, the seven new readers and the older ones on a hand-made joined
trace, the cell on reason-sat as PR 60 left the file (pinned below:
PR 63 appended after it), the rehearsal cell at --trace 0 and 2.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_phi4flash_family")

from benchmarks.tests.test_phi4flash_family import *  # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 60's cell and the file's end as PR 60 left them
# (tests/benchmark_as_of.py): PR 63 appended after them
test_the_cell_and_reason_sat_as_it_stands = pinned(
    test_the_cell_and_reason_sat_as_it_stands, 60)    # noqa: F821
