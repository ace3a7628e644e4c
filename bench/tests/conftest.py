"""The benchmark's own tests, run on the CPU at small sizes:

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _no_persistent_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
