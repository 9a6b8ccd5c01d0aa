import os

# Keep smoke tests on 1 device (the dry-run, and ONLY the dry-run, forces 512).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _reset_kernel_tiles():
    """Restore default tiles between tests: a test that installs tuned
    tiles must not leak them into every later test."""
    yield
    from repro.kernels.autotune import reset_tiles
    reset_tiles()
