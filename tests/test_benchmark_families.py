"""The benchmark's family seam and its families, in tier-1: the cases
of benchmarks/tests/test_families.py (the seam), test_mixtral_family.py
(the rehearsal family), test_olmoe_family.py (the OLMoE family: the
program against the plain reference at the toy size, the byte counts
and the four readers against hand counts, the rehearsal cell end to
end) and test_solar_open2_family.py (the Solar-Open2 family: the
configuration against its published copy, a chip's share against the
reference, byte counts, three readers, doc-sat, the rehearsal cell)
and test_axk1_family.py (the A.X-K1 family: the configuration against
its published copy, the seeded weights, YaRN by hand, the near-tie
rule, byte counts, three readers, longdoc-sat, the rehearsal cell),
collected here so that the suite the driver runs guards them.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

_FILES = ("benchmarks.tests.test_families",
          "benchmarks.tests.test_mixtral_family",
          "benchmarks.tests.test_olmoe_family",
          "benchmarks.tests.test_solar_open2_family",
          "benchmarks.tests.test_axk1_family")
pytest.register_assert_rewrite(*_FILES)

from benchmarks.tests.test_families import *          # noqa: E402,F401,F403
from benchmarks.tests.test_mixtral_family import *    # noqa: E402,F401,F403
from benchmarks.tests.test_olmoe_family import *      # noqa: E402,F401,F403
from benchmarks.tests.test_solar_open2_family import *  # noqa: E402,F401,F403
from benchmarks.tests.test_axk1_family import *       # noqa: E402,F401,F403

# Recorded without tier-1's low-optimisation XLA flags (tests/conftest.py),
# under which the CPU draws a normal's last bits differently: the digests
# are checked where they were recorded, by `python -m pytest
# benchmarks/tests`.
del test_seeded_weights_are_the_parents_bit_for_bit    # noqa: F821
