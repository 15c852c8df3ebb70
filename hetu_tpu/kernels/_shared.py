"""What every Pallas kernel of this package asks of the backend."""

import jax

# lanes of a TPU vector register: the minor tile of every VMEM buffer
_LANES = 128


def _use_interpret():
    """Off the TPU a kernel runs in interpret mode (tests replace this
    name in the kernel's own module to lower for a described chip)."""
    return jax.default_backend() != "tpu"
