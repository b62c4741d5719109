"""Device-count-agnosticism guard for the planner
(serve/scheduler.py ALLOWED_IMPORTS).

The tensor-parallel engine (serve/sharding.py) relies on one
``StepPlan`` driving a 1-chip and an N-way engine identically; that
only holds if the planner literally cannot see device topology. Two
enforcement angles:

- static: AST-walk the module — every import must be in the declared
  ALLOWED_IMPORTS contract (no jax, no jaxlib, no numpy, nothing that
  could read a device count);
- dynamic: import the module standalone in a subprocess and assert
  jax/jaxlib never entered sys.modules, then run a plan_step to prove
  the standalone module is the real planner, not a stub.
"""
import ast
import json
import subprocess
import sys
from pathlib import Path

SCHEDULER = (Path(__file__).resolve().parent.parent
             / "ray_tpu" / "serve" / "scheduler.py")


def _top_module(name: str) -> str:
    return name.split(".")[0]


def test_scheduler_imports_within_contract():
    from ray_tpu.serve.scheduler import ALLOWED_IMPORTS
    tree = ast.parse(SCHEDULER.read_text())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen.update(_top_module(a.name) for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            # relative imports would smuggle in package siblings
            assert node.level == 0, ast.dump(node)
            seen.add(_top_module(node.module))
    assert seen, "no imports found — wrong file?"
    assert seen <= set(ALLOWED_IMPORTS), (
        f"scheduler.py imports outside the device-count-agnosticism "
        f"contract: {sorted(seen - set(ALLOWED_IMPORTS))}")


def test_scheduler_never_loads_jax():
    """Load scheduler.py standalone by path (no ray_tpu package
    __init__, which legitimately imports jax) and prove the planner
    plans without jax/jaxlib/numpy ever appearing in sys.modules."""
    prog = f"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location(
    "planner", {str(SCHEDULER)!r})
mod = importlib.util.module_from_spec(spec)
sys.modules["planner"] = mod    # dataclasses resolves __module__
spec.loader.exec_module(mod)
bad = sorted(m for m in ("jax", "jaxlib", "numpy")
             if m in sys.modules)
slots = [mod.SlotView(sid=0, admit_seq=0, prompt_remaining=8,
                      owed=4, seeded=False),
         mod.SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=4, seeded=True)]
plan = mod.plan_step(slots, total_slots=4, prefill_chunk=16,
                     decode_chunk=4, max_run_ahead=64,
                     prefill_batch=4, eos_bounded=False)
print(json.dumps({{"bad": bad,
                   "prefill": len(plan.prefill),
                   "decode": plan.decode_steps}}))
"""
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout)
    assert res["bad"] == [], (
        f"planning pulled in device-aware modules: {res['bad']}")
    assert res["prefill"] >= 1 and res["decode"] >= 1
