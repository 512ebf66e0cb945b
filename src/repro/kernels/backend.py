"""The one backend probe that decides how a Pallas kernel runs.

Every kernel entry point takes ``interpret: Optional[bool] = None`` and
resolves it here: ``None`` compiles the kernel on a TPU and runs the
Pallas interpreter everywhere else (the CPU test suite).  A TPU never
interprets — asking for it there raises, naming the kernel, so a slow
interpreted kernel can never stand in for the compiled one on the chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool], kernel: str) -> bool:
    """Whether ``kernel`` runs in the Pallas interpreter."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            f"{kernel}: interpret=True on a TPU — the kernel must compile "
            "there (pass interpret=None)")
    return bool(interpret)
