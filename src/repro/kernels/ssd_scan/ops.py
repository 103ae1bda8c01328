"""Jitted wrapper for the SSD chunked-scan kernel, with a backward path:
the forward runs the Pallas kernel; the VJP differentiates the sequential-
recurrence reference (``lax.scan``) from the saved inputs — recompute-based,
so no per-chunk states are stored as residuals."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import ssd_scan
from .ref import reference_ssd


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a, b, c, chunk, interpret):
    return ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, a, b, c, chunk, interpret):
    return _ssd(x, dt, a, b, c, chunk, interpret), (x, dt, a, b, c)


def _ssd_bwd(chunk, interpret, residuals, g):
    x, dt, a, b, c = residuals
    _, vjp = jax.vjp(reference_ssd, x, dt, a, b, c)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, *, chunk: int = 256, interpret: bool | None = None):
    return _ssd(x, dt, a, b, c, chunk, interpret)
