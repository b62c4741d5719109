"""benchmarks/tests/test_granite_hybrid_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the Granite-4.0-H family: the configuration against its published copy
(``reduced`` the depth, the layer list, the experts held, the
vocabulary's slice and the page table's width), the program against the
reference at a share and the margin rule against the reference's eight
controls, the excusing of flipped positions, byte and FLOP counts by
kind of layer, the two new readers and the older ones the cell joins on
a hand-made joined trace, the cell on gen-sat as PR 65 left the file,
the rehearsal cell at --trace 2.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_granite_hybrid_family")

from benchmarks.tests.test_granite_hybrid_family import *  # noqa: E402,F401,F403
