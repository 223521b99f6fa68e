"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They import the benchmark as the package ``bench`` and the program from
``src``; nothing here needs a chip."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True, scope="module")
def _fresh_jit_caches():
    """Each module starts with empty jit caches, so one process does not
    pile up every module's programs."""
    import jax
    jax.clear_caches()
    yield
