"""The arrows point down: ``singa_tpu/tools/`` holds command lines over
the library, and nothing in the library reaches back up into it. Read
from the ``import`` statements (``ast``), so a docstring that names a
tool's command line is fine."""

import ast
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parent.parent / "singa_tpu"
UNITS = sorted(
    p.name for p in PKG.iterdir()
    if p.name not in ("tools", "__pycache__")
    and (p.is_dir() or p.suffix == ".py")
)


def _imported(path: pathlib.Path) -> set[str]:
    """Absolute dotted names of everything ``path`` imports, relative
    imports resolved against its own package."""
    here = path.relative_to(PKG.parent).with_suffix("").parts[:-1]
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = list(here[: len(here) - node.level + 1]) if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("unit", UNITS)
def test_nothing_below_tools_imports_tools(unit):
    root = PKG / unit
    files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
    assert files
    up = {
        f"{f.relative_to(PKG)}: {name}"
        for f in files
        for name in _imported(f)
        if name == "singa_tpu.tools" or name.startswith("singa_tpu.tools.")
    }
    assert not up, sorted(up)
