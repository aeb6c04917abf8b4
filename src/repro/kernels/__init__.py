"""Pallas kernels (TPU target), each with a jnp reference.

Every ops layer selects its path with an ``impl`` string: ``'ref'`` (jnp),
``'pallas'`` (the Pallas interpreter, a CPU-validation choice) or
``'pallas_tpu'`` (the compiled Mosaic kernel).
"""

from __future__ import annotations

import jax

from repro.core import errors


def checked_interpret(interpret: bool) -> bool:
    """Pass ``interpret`` through to ``pallas_call``, refusing it on a TPU
    backend: there the interpreter would stand in for the compiled kernel
    and hide that the kernel never ran on the chip."""

    errors.check(
        not (interpret and jax.default_backend() == "tpu"),
        errors.ErrorClass.ERR_UNSUPPORTED_OPERATION,
        "Pallas interpret mode validates kernels on the CPU; on a TPU backend "
        "select the compiled kernel (impl='pallas_tpu')",
    )
    return interpret
