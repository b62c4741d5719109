"""benchmarks/tests run on the CPU: `python -m pytest benchmarks/tests`
from the repo's root. They are outside tier-1's tests/."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
