"""The one switch between interpreted and compiled Pallas kernels."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    An explicit ``interpret`` wins (the tests' oracles pass ``True``).
    Otherwise kernels are interpreted only on the CPU backend; on any
    accelerator they compile, and a kernel the compiler refuses fails
    with the compiler's error instead of running interpreted.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


__all__ = ["resolve_interpret"]
