"""Pallas TPU kernels (flash attention, grouped GEMM, SSD scan, RMSNorm)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` flag every kernel wrapper passes to ``pallas_call``.

    An explicit choice always wins.  Otherwise the kernel compiles on a TPU
    backend and runs in interpret mode on any other (the CPU correctness
    path), so a TPU never interprets unless the caller asks.  Code that
    lowers for a TPU from a CPU process passes ``interpret=False``."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
