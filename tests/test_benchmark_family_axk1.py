"""benchmarks/tests/test_axk1_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the A.X-K1 family: the configuration against its published copy, the
seeded weights, YaRN by hand, the near-tie rule, byte counts, three
readers, longdoc-sat, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_axk1_family")

from benchmarks.tests.test_axk1_family import *    # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 34's cell, and the file's last three readers as PR 34 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_longdoc_sat = pinned(
    test_the_cell_and_longdoc_sat, 34)    # noqa: F821
