"""Shared fixtures of the benchmark's CPU tests."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def no_cache(monkeypatch):
    """Keep CPU test runs out of the persistent compilation cache."""
    from bench import harness
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


@pytest.fixture
def cpu_chip():
    """A chip check that accepts the CPU, for driving runs in tests."""
    import jax
    return lambda chips: jax.devices()[:chips]
