import os

# Tests run on the single real CPU device; ONLY subprocess-based distribution
# tests force a device count (never set globally here, per the dry-run
# contract).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
