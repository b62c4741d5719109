"""benchmarks/tests/test_deepseek_v32_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the DeepSeek-V3.2 family: the configuration against its published
copy, the program against the reference through both pools, the
near-tie rule with the groups' boundary, byte counts, the seven new
readers on a hand-made joined trace, the cell on longdoc-sat as it
stood when PR 56 left the file (tests/benchmark_as_of.py), the
rehearsal cell at --trace 0 and 2.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_deepseek_v32_family")

from benchmarks.tests.test_deepseek_v32_family import *  # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 56's cell and the file's end as PR 56 left them
# (tests/benchmark_as_of.py)
test_the_dsv32_cell_and_longdoc_sat_as_it_stands = pinned(
    test_the_dsv32_cell_and_longdoc_sat_as_it_stands, 56)    # noqa: F821
