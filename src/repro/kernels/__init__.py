"""Pallas TPU kernels for the fractal sort and the layers built on it.

Every kernel takes ``interpret=None`` and resolves it through
:func:`default_interpret`: compiled on a TPU backend, interpreted
elsewhere, unless the caller asks for one or the other.
"""

import jax


def default_interpret(interpret=None) -> bool:
    """A kernel's interpret flag: ``interpret`` when given, else True
    exactly when the default backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
