"""Fixtures of the benchmark's tests: the repository root on ``sys.path``
(for ``bench``) and a CPU-sized benchmark root built in a temporary
directory from ``fixtures/tiny`` and the benchmark's own metric readers and
traffic kinds."""
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(ROOT))


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(FIXTURES / "tiny", root)
    for part in ("metrics", "kinds"):
        shutil.copytree(ROOT / "bench" / part, root / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep CPU compiles out of the checkout's persistent cache."""
    from bench import harness

    monkeypatch.setattr(harness, "use_compile_cache", lambda: "")
