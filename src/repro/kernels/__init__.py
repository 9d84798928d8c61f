"""Pallas kernels of the similarity engines (packages ``mgemm``,
``mgemm_levels``, ``popgemm``, ``czek3``)."""
import jax


def interpret_mode() -> bool:
    """Default ``interpret`` flag of every kernel wrapper: Mosaic-compiled on
    a TPU backend, the Pallas interpreter on any other (the CPU tests)."""
    return jax.default_backend() != "tpu"
