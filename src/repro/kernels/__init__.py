"""Pallas kernels: each package holds kernel.py, the jitted ops wrappers
and the pure-jnp reference its tests compare against.

Interpret mode follows from the platform: kernels compile on the TPU and
run in the Pallas interpreter everywhere else.  Nothing on the TPU may run
a kernel interpreted.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag a ``pallas_call`` receives.

    ``None`` derives it from ``jax.default_backend()``.  ``False`` forces
    the compiled kernel (an ahead-of-time compile for a described TPU runs
    from a CPU process).  ``True`` on the TPU raises."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on the TPU; "
                         "kernels run compiled there")
    return bool(interpret)
