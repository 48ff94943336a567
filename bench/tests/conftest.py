import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture
def fresh_programs(monkeypatch):
    """Programs traced with a fault must not outlive the test, and no
    test writes the persistent compilation cache."""
    import jax

    from bench import harness

    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    jax.clear_caches()
    yield
    jax.clear_caches()
