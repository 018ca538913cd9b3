"""How a Pallas kernel is lowered: compiled on a TPU, interpreted elsewhere.

The choice is made when the kernel is traced, from the platform JAX runs
on — never when a module is imported, and never from an environment
variable.  On a TPU every kernel is compiled by Mosaic; no caller can ask
for interpret mode there.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU.

    An explicit ``interpret=False`` compiles for the TPU even off-chip
    (the compile rehearsal in ``tests/test_tpu_compile.py``); an explicit
    ``interpret=True`` on a TPU is refused.
    """
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError("Pallas kernels never run in interpret mode on a "
                         "TPU; pass interpret=None")
    return bool(interpret)
