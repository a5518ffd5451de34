"""models/decoder is the seam between the six decoder language models:
what they share comes from it, and none of them imports another."""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = ("keye_vl2", "zaya", "mellum2", "olmo_hybrid", "trinity",
          "glm4_moe_lite")


def _imported_modules(tree):
    """Every module an ``import`` or ``from ... import`` of ``tree``
    names, a ``from package import name`` counted as ``package.name``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            yield base
            yield from (f"{base}.{alias.name}" for alias in node.names)


@pytest.mark.parametrize("model", MODELS)
def test_no_decoder_model_imports_another(model):
    path = os.path.join(ROOT, "parallax_tpu", "models", f"{model}.py")
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    siblings = {m for m in MODELS if m != model}
    found = [name for name in _imported_modules(tree)
             if name.rsplit(".", 1)[-1] in siblings]
    assert found == [], f"models/{model}.py imports {found}"
