"""When the Pallas kernels run in interpreter mode.

Interpret mode lowers a kernel to ordinary HLO: slow, partitionable by
GSPMD, and nothing like what runs on a chip. It is for the CPU tests, so
it is selected only when the CPU platform was *asked for by name*
(``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms", "cpu")``).
A TPU that failed to initialise leaves the platform unnamed; the kernels
then lower through Mosaic and fail loudly instead of quietly interpreting.
"""

import jax


def use_interpret() -> bool:
    platforms = (jax.config.jax_platforms or "").split(",")
    return platforms[0].strip() == "cpu"
