"""Jit'd public wrapper for the SSD scan.

Dispatches between the Pallas kernel (TPU target / interpret validation) and
the chunked jnp form (CPU compile path for full models).  Backward pass:
``custom_vjp`` recomputing through the chunked reference — SSD residuals are
O(L·state), recompute keeps memory at activations-only.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan import kernel as _kernel
from repro.kernels.ssd_scan import ref as _ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, B, C, chunk, impl):
    return _forward(x, dt, A, B, C, chunk, impl)


def _forward(x, dt, A, B, C, chunk, impl):
    if impl in ("pallas", "pallas_tpu"):
        return _kernel.ssd_scan_fwd(
            x, dt, A, B, C, chunk=chunk, interpret=impl == "pallas"
        )
    y, _ = _ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    return y


def _fwd(x, dt, A, B, C, chunk, impl):
    return _forward(x, dt, A, B, C, chunk, impl), (x, dt, A, B, C)


def _bwd(chunk, impl, res, g):
    x, dt, A, B, C = res

    def recompute(x, dt, A, B, C):
        y, _ = _ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
        return y

    _, vjp = jax.vjp(recompute, x, dt, A, B, C)
    return vjp(g)


_ssd.defvjp(_fwd, _bwd)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, impl: str = "ref"):
    """y = SSD(x, dt, A, B, C); shapes as in :mod:`.ref`."""

    return _ssd(x, dt, A, B, C, chunk, impl)


ssd_decode_step = _ref.ssd_decode_step
