"""Pallas TPU kernels for the performance-critical compute layers.

* ``sgns_update`` — fused SGNS forward+backward on gathered rows
  (pl.pallas_call + BlockSpec VMEM tiling); ``ops`` holds the jit'd
  wrappers (padding, gather/scatter); ``ref`` the pure-jnp oracles.
  Powers the ``pallas`` update engine.
* ``sgns_fused_pipe`` — the whole SGNS step in one kernel against
  HBM-resident tables: a pure-JAX block planner, a pipelined ring of
  row DMAs and a VMEM-pinned hot prefix (``hot_rows = 0`` is the
  pure-DMA case). Powers the ``pallas_fused_tiered`` update engine.
* ``alias_draw`` — the counter-PRNG alias draw of that step's
  negatives, which its planner and the tests replay.
* ``swa_decode`` — flash-style single-token sliding-window decode
  attention (online softmax, VMEM scratch accumulators) — the hot op of
  the long_500k shape for dense archs.

Every entry point takes ``interpret=None``: compiled by Mosaic on a TPU,
interpreted elsewhere (``mode.resolve_interpret``). The CPU tests run
them in interpret mode.
"""

from repro.kernels.ops import (
    sgns_row_grads,
    sgns_apply_step,
    make_row_grad_fn,
)
from repro.kernels.alias_draw import (
    fused_negative_ids,
    counter_uniforms,
)
from repro.kernels.ref import sgns_row_grads_ref, swa_decode_ref
from repro.kernels.swa_decode import swa_decode_kernel

__all__ = [
    "sgns_row_grads",
    "sgns_apply_step",
    "make_row_grad_fn",
    "fused_negative_ids",
    "counter_uniforms",
    "sgns_row_grads_ref",
    "swa_decode_ref",
    "swa_decode_kernel",
]
