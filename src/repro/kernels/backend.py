"""Kernel execution-backend policy: when do Pallas kernels run interpreted?

Mosaic lowering needs a TPU, so off-TPU (CPU CI) every kernel runs through
the Pallas interpreter, and on a TPU every kernel runs compiled.  One
precedence order decides it:

1. an explicit non-None override — either an ``interpret=`` argument at a
   kernel call site (tests pin interpreter semantics this way) or
   ``ModelConfig.kernel_interpret`` threaded through ``kernels/ops.py`` by
   the model layer (both arrive here as ``override``),
2. the ``REPRO_KERNEL_INTERPRET`` environment variable ("0" forces
   compiled, anything else forces interpreted) — consulted only when no
   explicit override was given,
3. auto-detection: interpret only off-TPU.

Forcing the interpreter on a TPU backend raises: the interpreter is orders
of magnitude slower than Mosaic, and a run that silently took it would
report interpreter numbers as chip numbers.  A backend that fails to
initialise raises too, rather than being read as "not a TPU".
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_ENV = "REPRO_KERNEL_INTERPRET"


def on_tpu() -> bool:
    """True when the default jax backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(override: Optional[bool] = None) -> bool:
    """The ``interpret=`` value a Pallas kernel should actually use.

    ``override`` is a call-site / config override (``None`` = no opinion).
    Precedence: explicit override > ``REPRO_KERNEL_INTERPRET`` env var >
    backend auto-detection (interpret iff not on TPU).  Raises
    ``ValueError`` when the result would run the interpreter on a TPU.
    """
    if override is None and _ENV in os.environ:
        override = os.environ[_ENV] != "0"
    if override is None:
        return not on_tpu()
    override = bool(override)
    if override and on_tpu():
        raise ValueError(
            f"Pallas kernels forced to interpret mode on a TPU backend "
            f"(kernel_interpret / interpret= / {_ENV}): the TPU runs the "
            f"Mosaic-compiled kernels; drop the override")
    return override
