"""CPU tests of the benchmark: ``python -m pytest bench``.

They run on the CPU at tiny sizes, with JAX's persistent compile cache off,
and import no TPU library."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """The harness turns the persistent cache on; the tests keep nothing."""
    import jax
    from bench import harness

    configure = harness.configure_jax

    def configure_without_cache():
        configure()
        jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(harness, "configure_jax", configure_without_cache)
