"""benchmarks/tests/test_phi4flash_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Phi-4-mini-flash family: the configuration against its published
copy (``reduced`` the page table's width alone, every ``assumed`` item
named in the family file), the program against the reference and the
margin rule against the reference's controls, byte counts by kind of
layer, the seven new readers and the older ones on a hand-made joined
trace, the cell on reason-sat as it stands (PR 60's row is the table's
last: the file itself), the rehearsal cell at --trace 0 and 2.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_phi4flash_family")

from benchmarks.tests.test_phi4flash_family import *  # noqa: E402,F401,F403
