"""Package integrity: every module under ``repro`` imports, and nothing in
the tree names a subsystem that the package no longer holds."""
import importlib
import pathlib
import pkgutil
import re

import repro

ROOT = pathlib.Path(__file__).resolve().parents[1]
# built from parts so that this file does not match its own search
REMOVED = tuple("repro." + name for name in ("models", "runtime", "launch", "sharding"))


def test_every_module_imports_and_no_removed_package_is_named():
    names = [m.name for m in pkgutil.walk_packages(repro.__path__, "repro.")
             if not m.name.endswith(".__main__")]
    assert "repro.kernels.schedule_dp" in names
    for name in names:
        importlib.import_module(name)
    pattern = re.compile("|".join(re.escape(n) + r"\b" for n in REMOVED))
    hits = []
    for top in ("src", "tests", "examples", "benchmarks"):
        for path in sorted((ROOT / top).rglob("*")):
            if not path.is_file() or "__pycache__" in path.parts:
                continue
            for lineno, line in enumerate(
                    path.read_text(errors="ignore").splitlines(), 1):
                if pattern.search(line):
                    hits.append(f"{path.relative_to(ROOT)}:{lineno}: {line.strip()}")
    assert not hits, "\n".join(hits)
    for name in REMOVED:
        assert not (ROOT / "src" / name.replace(".", "/")).exists(), name
