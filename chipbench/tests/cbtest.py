"""Shared set-up of the benchmark's tests: the benchmark and the program on
``sys.path``, and the program's block autotuner kept off any file outside
the test.  Each test module imports the fixture by name."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def isolated_autotune(tmp_path, monkeypatch):
    """Keep the program's block autotuner off any file outside the test."""
    from repro.kernels import autotune
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "autotune.json"))
    autotune.clear_memory_cache()
    yield
    autotune.clear_memory_cache()
