"""benchmarks/tests/test_ouro_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Ouro family: the configuration whole against its published copy,
the program against the reference and the margin rule against the
reference's controls, byte counts with the weights once a pass, the two
readers on a hand-made joined trace with a nested loop, the cell on
chat-sat as it stands, the rehearsal cell.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_ouro_family")

from benchmarks.tests.test_ouro_family import *    # noqa: E402,F401,F403

from benchmark_as_of import pinned    # noqa: E402

# PR 46's cell and the file's end as PR 46 left them
# (tests/benchmark_as_of.py)
test_the_cell_and_chat_sat_as_it_stands = pinned(
    test_the_cell_and_chat_sat_as_it_stands, 46)    # noqa: F821
