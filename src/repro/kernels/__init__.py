"""Pallas TPU kernels, each with a jnp reference (``ref.py``) and a jitted,
differentiable wrapper (``ops.py``).

Every kernel takes ``interpret=None``: the one switch below then compiles
it for the chip when JAX's backend is a TPU and runs it in the Pallas
interpreter everywhere else (the CPU tests). Pass ``interpret=False`` only
to compile ahead of time for a described chip from a host without one.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if given, else True unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
