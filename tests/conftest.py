import os

# Tests run single-device (the dry-run sets its own 512-device flag in its
# own process). Cap compilation parallelism noise on the 1-core container.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
from hypothesis import settings

jax.config.update("jax_enable_x64", False)


# Property tests compile a kernel on their first example, which no
# per-example deadline survives.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")
