"""benchmarks/tests/test_sdar_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the SDAR family: the configuration against its published copy, the
served path against ``reference.generate``, the replay that hands the
margin rule its logits and its controls, the byte and FLOP counts, the
five new readers on a hand-made joined trace and event log, the cell on
gen-sat as PR 63 left the file (pinned below: PR 65 appended after it),
the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_sdar_family")

from benchmarks.tests.test_sdar_family import *    # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 63's cell and the file's end as PR 63 left them
# (tests/benchmark_as_of.py): PR 65 appended after them
test_the_cell_and_gen_sat_as_it_stands = pinned(
    test_the_cell_and_gen_sat_as_it_stands, 63)    # noqa: F821
