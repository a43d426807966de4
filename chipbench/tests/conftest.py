import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _private_compile_cache(tmp_path, monkeypatch):
    """Each test compiles into its own cache, off the checkout's."""
    from chipbench import bench
    monkeypatch.setattr(bench, "CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
