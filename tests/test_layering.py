"""Import layering of the package: the kernels sit below the models,
the models below serving, and neither the kernels nor the models reach
up into a layer above them. Read from each module's syntax tree, so an
import inside a function counts as much as one at the top."""
import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _imported(pkg):
    """(module path, imported name) of every import under repro/<pkg>;
    ``from a import b`` yields both ``a`` and ``a.b``."""
    for path in sorted((SRC / pkg).rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    yield path, alias.name
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = ("repro",) + path.relative_to(SRC).parent.parts
                    parts = parts[:len(parts) - node.level + 1]
                    base = ".".join(parts + ((base,) if base else ()))
                yield path, base
                for alias in node.names:
                    yield path, f"{base}.{alias.name}"


@pytest.mark.parametrize("pkg,upper", [
    ("models", "repro.serve"),
    ("kernels", "repro.serve"),
    ("kernels", "repro.train"),
    ("kernels", "repro.models"),
    ("sharding", "repro.train"),
])
def test_no_upward_imports(pkg, upper):
    bad = sorted({f"{p.relative_to(SRC)}: {name}"
                  for p, name in _imported(pkg)
                  if name == upper or name.startswith(upper + ".")})
    assert not bad, bad
