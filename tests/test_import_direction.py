"""The package serves and fits; the programs that measure it import the
package, never the other way: no module under ``keystone_tpu/`` imports a
benchmark program (``benchmark/``, ``chip_smoke.py``, a ``bench`` module),
at top level or inside a function."""

import ast
import pathlib

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "keystone_tpu"
PROGRAMS = {"bench", "benchmark", "chip_smoke"}


def _measures(module: str) -> bool:
    parts = module.split(".")
    return parts[0] in PROGRAMS or parts[-1] == "bench"


def test_no_package_module_imports_a_benchmark_program():
    found = []
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                # ``from package import module`` names the module in
                # the alias, not in ``module``
                modules = [base] + [
                    f"{base}.{alias.name}".lstrip(".")
                    for alias in node.names
                ]
            else:
                continue
            found += [
                f"{path.relative_to(PACKAGE.parent)}:{node.lineno}: {m}"
                for m in modules if m and _measures(m)
            ]
    assert not found, "\n".join(found)
