"""Arrows one way up: ``utils < state < ops < parallel < models < serving <
analysis`` (``docs/internal_mechanism.md``).  Every import of the three layers
below the serving engine is read off the AST, function bodies included; an
import of a layer above the file's own is an upward edge.  None may reach
``serving``, and the upward edges that exist are listed here by name."""

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parent.parent / "accelerate_tpu"
ORDER = ("utils", "state", "ops", "parallel", "models", "serving", "analysis")

# file -> (layer imported, names imported): every upward edge there is
KNOWN_UPWARD = {
    ("ops/collective_matmul.py", "parallel",
     ("axis_index", "axis_size", "partial_manual_kwargs", "ring_permute")),
    ("ops/fused_xent.py", "parallel", ("BATCH_AXES", "SEQ_AXES", "_axis_size")),
    ("ops/precision.py", "parallel", ("path_str",)),
    ("parallel/hierarchical.py", "analysis", ("iter_eqns",)),
    ("parallel/pipeline_parallel.py", "models", ("RMSNorm",)),
    ("parallel/sequence_parallel.py", "models", ("native_attention",)),
}


def package_imports(path: Path):
    """(layer, imported names) of every ``accelerate_tpu`` import in ``path``,
    relative or absolute, at any depth of the file."""
    here = list(path.relative_to(PACKAGE.parent).parts[:-1])
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            module = node.module.split(".") if node.module else []
            target = here[:len(here) - (node.level - 1)] + module if node.level else module
            names = tuple(sorted(a.name for a in node.names))
            if target == ["accelerate_tpu"]:        # ``from .. import x, y``: a layer a name
                for name in names:
                    yield name, ()
            elif target[:1] == ["accelerate_tpu"]:
                yield target[1], names
        elif isinstance(node, ast.Import):
            for alias in node.names:
                parts = alias.name.split(".")
                if parts[0] == "accelerate_tpu" and len(parts) > 1:
                    yield parts[1], ()


def upward_edges(layer: str):
    rank = ORDER.index(layer)
    for path in sorted((PACKAGE / layer).rglob("*.py")):
        for target, names in package_imports(path):
            if target in ORDER and ORDER.index(target) > rank:
                yield path.relative_to(PACKAGE).as_posix(), target, names


@pytest.mark.parametrize("layer", ["ops", "parallel", "models"])
def test_nothing_below_the_engine_imports_serving(layer):
    found = [(f, names) for f, target, names in upward_edges(layer) if target == "serving"]
    assert not found, f"{layer}/ imports accelerate_tpu.serving: {found}"


def test_the_upward_edges_are_exactly_the_known_ones():
    found = {edge for layer in ("ops", "parallel", "models") for edge in upward_edges(layer)}
    assert found == KNOWN_UPWARD, (
        f"new upward imports {sorted(found - KNOWN_UPWARD)}; "
        f"listed but gone {sorted(KNOWN_UPWARD - found)}")
