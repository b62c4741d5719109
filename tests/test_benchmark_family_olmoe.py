"""benchmarks/tests/test_olmoe_family.py in tier-1, in a file of
its own: ``--dist loadfile`` spreads the families over the workers, and
no two families' cases of one name shadow each other
(tests/test_benchmark_families.py holds both to it):
the OLMoE family: the program against the plain reference at the toy
size, the byte counts and the four readers against hand counts, the
rehearsal cell end to end.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_olmoe_family")

from benchmarks.tests.test_olmoe_family import *    # noqa: E402,F401,F403
