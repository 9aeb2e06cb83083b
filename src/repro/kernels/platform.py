"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted on
the CPU.

The choice is a fact of the platform a call is lowered for, not a setting:
:func:`pallas_call` stages both forms and ``jax.lax.platform_dependent``
keeps the one that matches the lowering platform, so a TPU never runs an
interpreted kernel and the CPU (the test suite) never asks Mosaic for one.
Any other platform fails at lowering time.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)`` whose ``interpret`` mode follows the
    platform: compiled on ``tpu``, interpreted on ``cpu``."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)

    return call
