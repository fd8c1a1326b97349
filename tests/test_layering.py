"""Which package may import which, read from the source with ``ast``.

The arrows point one way: ``ops`` <- ``parallel`` (aggregation over decode
lanes; knows nothing of pages) <- ``resident`` (pool, gather, scan) <-
``query``. The resident pool's page format has one reader outside
``pool.py``, ``resident/gather.py``, so the next change to the gather opens
one package.
"""

import ast
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PKG = ROOT / "m3_tpu"


def _imports(path: Path):
    """(module, name) for every ``from module import name`` and (module,
    None) for every ``import module`` of ``path``, function-local ones
    included, relative modules resolved against the file's package."""
    package = list(path.relative_to(ROOT).with_suffix("").parts[:-1])
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            for alias in node.names:
                yield module, alias.name


def _under(module: str, name, prefix: str) -> bool:
    full = module if name is None else f"{module}.{name}"
    return module == prefix or module.startswith(prefix + ".") or full == prefix


def _parallel_knows_no_pages():
    # ops.sideplane is the packed side-page row: the pool's format too
    barred = ("m3_tpu.resident", "m3_tpu.query", "m3_tpu.storage",
              "m3_tpu.ops.sideplane")
    for path in sorted((PKG / "parallel").rglob("*.py")):
        for module, name in _imports(path):
            if any(_under(module, name, p) for p in barred):
                yield f"{path.relative_to(ROOT)}: {module} {name or ''}"


def _no_private_names_across_packages():
    for path in sorted(PKG.rglob("*.py")):
        for module, name in _imports(path):
            if not name or not name.startswith("_"):
                continue
            for owner in ("resident", "parallel"):
                inside = (PKG / owner) in path.parents
                if _under(module, None, f"m3_tpu.{owner}") and not inside:
                    yield f"{path.relative_to(ROOT)}: {module} {name}"


def _plan_does_not_reach_around_resident():
    for module, name in _imports(PKG / "query" / "plan.py"):
        if _under(module, name, "m3_tpu.parallel"):
            yield f"m3_tpu/query/plan.py: {module} {name or ''}"


@pytest.mark.parametrize("rule", [
    _parallel_knows_no_pages,
    _no_private_names_across_packages,
    _plan_does_not_reach_around_resident,
], ids=lambda f: f.__name__.strip("_"))
def test_layering(rule):
    assert list(rule()) == []
