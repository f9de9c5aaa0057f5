"""Pallas execution mode: compiled by Mosaic on a TPU, interpreted elsewhere."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` as given; ``None`` means interpret mode everywhere
    but on a TPU backend, where the kernel is compiled by Mosaic."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
