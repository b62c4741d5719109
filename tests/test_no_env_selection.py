"""Nothing outside a call's own arguments chooses an implementation:
the modules between ``LlamaDeployment`` and the kernels read no
environment variable at all (PR 30 removed the four that did:
a constructor argument or a constant already made each decision).
Deployment settings (addresses, trace and flight directories) are
read elsewhere and are not selections.
"""
import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"
_ENV_NAMES = {"environ", "environb", "getenv", "putenv"}


def _env_reads(path):
    tree = ast.parse(path.read_text(), filename=str(path))
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in _ENV_NAMES:
            hits.append((path.name, node.lineno, node.attr))
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            hits += [(path.name, node.lineno, a.name)
                     for a in node.names if a.name in _ENV_NAMES]
    return hits


@pytest.mark.parametrize("where", ["models", "ops", "serve/engine.py",
                                   "serve/step_programs.py",
                                   "serve/round_accounts.py",
                                   "serve/llm.py"])
def test_serving_path_reads_no_environment(where):
    target = ROOT / where
    files = sorted(target.rglob("*.py")) if target.is_dir() else [target]
    assert files, target
    hits = [h for f in files for h in _env_reads(f)]
    assert not hits, hits
