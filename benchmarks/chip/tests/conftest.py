"""The benchmark's self-tests run on the CPU, from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They are outside the repository's ``testpaths``."""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    """Keep CPU compiles out of the checkout's cache directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.getbasetemp() / "jax_cache"))
