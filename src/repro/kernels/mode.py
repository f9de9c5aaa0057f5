"""Pallas execution mode: compiled by Mosaic on a TPU, interpreted elsewhere."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` as given; ``None`` means interpret mode everywhere
    but on a TPU backend, where the kernel is compiled by Mosaic."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def require_interpret(interpret: bool | None, kernel: str, reason: str) -> bool:
    """:func:`resolve_interpret` for a kernel that has no Mosaic lowering:
    raises, naming ``reason``, where it would be compiled."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            f"{kernel} does not compile under Mosaic: {reason}. It runs in "
            f"interpret mode only; on a TPU use the pallas_fused_pipe or "
            f"pallas_fused_tiered engine")
    return True
