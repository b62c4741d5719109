"""benchmarks/tests/test_kimi_linear_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Kimi-Linear family: the configuration against its published copy,
the program against the reference at a share, seeded and balanced
weights, byte counts by kind of layer, the four readers on a hand-made
joined trace, gen-sat, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_kimi_linear_family")

from benchmarks.tests.test_kimi_linear_family import *  # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 39's cell and the file's end as PR 39 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_gen_sat = pinned(
    test_the_cell_and_gen_sat, 39)    # noqa: F821
