import os
import sys

# Tests run on the single real CPU device (the 512-device override is
# exclusive to launch/dryrun.py). Keep compilation single-threaded-ish to
# avoid oversubscribing the 1-core container.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
