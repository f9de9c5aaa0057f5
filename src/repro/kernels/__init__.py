"""Pallas TPU kernels for the performance-critical compute layers.

* ``sgns_update`` — fused SGNS forward+backward on gathered rows
  (pl.pallas_call + BlockSpec VMEM tiling); ``ops`` holds the jit'd
  wrappers (padding, gather/scatter); ``ref`` the pure-jnp oracles.
  Powers the ``pallas`` update engine.
* ``sgns_fused`` — the whole SGNS step in one kernel: in-kernel alias
  negative sampling (counter-based PRNG), forward, row grads and
  scatter-add apply in a single VMEM pass. Powers the ``pallas_fused``
  update engine.
* ``sgns_fused_hbm`` / ``sgns_fused_pipe`` / ``sgns_fused_tiered`` — the
  step against HBM-resident tables: the serial-DMA oracle, the
  pipelined kernel, and its frequency-tiered entry point.
* ``swa_decode`` — flash-style single-token sliding-window decode
  attention (online softmax, VMEM scratch accumulators) — the hot op of
  the long_500k shape for dense archs.

Every entry point takes ``interpret=None``: compiled by Mosaic on a TPU,
interpreted elsewhere (``mode.resolve_interpret``). The CPU tests run
them in interpret mode. On a TPU, ``sgns_update``, ``sgns_fused_pipe``
and ``sgns_fused_tiered`` compile; ``sgns_fused`` and ``sgns_fused_hbm``
gather by vectors of ids, which Mosaic cannot lower, and raise when
built to compile (``mode.require_interpret``).
"""

from repro.kernels.ops import (
    sgns_row_grads,
    sgns_apply_step,
    make_row_grad_fn,
)
from repro.kernels.sgns_fused import (
    sgns_fused_step,
    sample_negatives_fused,
    fused_negative_ids,
    counter_uniforms,
)
from repro.kernels.ref import sgns_row_grads_ref, swa_decode_ref
from repro.kernels.swa_decode import swa_decode_kernel

__all__ = [
    "sgns_row_grads",
    "sgns_apply_step",
    "make_row_grad_fn",
    "sgns_fused_step",
    "sample_negatives_fused",
    "fused_negative_ids",
    "counter_uniforms",
    "sgns_row_grads_ref",
    "swa_decode_ref",
    "swa_decode_kernel",
]
