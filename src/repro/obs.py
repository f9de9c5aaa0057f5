"""The program's tracing: the one place its span, scope, kernel and
counter names are defined, and the two instruments that are not JAX's
own.

* Host spans (:func:`span`) are ``jax.profiler.TraceAnnotation`` events:
  they land on the profiler's host planes, on the clock the device
  planes share, so an idle gap of the device can be put down to what
  the host was doing. While the profiler is not tracing, a span costs
  one C++ check and keeps nothing. Every span name starts with ``repro.``.
  They open per chunk, never per sentence, pair or step, and carry the
  chunk index (or the chunk's first step) as their id.
* Device scopes are ``jax.named_scope`` names: they change only the
  ``op_name`` metadata of the ops they cover, which a device trace
  reports beside each op.
* Counters (:func:`count`, :func:`read`, :func:`reset`) are kept in
  memory by name. A device value is kept as a reference and summed on
  the device every :data:`FOLD_EVERY` values; nothing here reads a
  device value back until :func:`read`.

What reads each name: the per-layer metrics of the chip benchmark
(``benchmarks/chip/metrics/``). The spans and scopes have no reader
there yet: the benchmark's trace reduction (``benchmarks/chip/trace.py``)
keeps neither the ops' scope paths nor these spans. PERF.md names the
metrics meant to read them.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

# host spans, for an ingestion busy share and the naming of idle gaps
INGEST_EXTRACT = "repro.ingest.extract"   # one chunk filled from the streams
INGEST_H2D = "repro.ingest.h2d"           # one chunk's host→device copy
INGEST_WAIT = "repro.ingest.wait"         # the consumer waiting for a chunk
INGEST_WALK = "repro.ingest.walk"         # one block of graph walks and its pairs
TRAIN_DISPATCH = "repro.train.dispatch"   # one chunk's epoch call enqueued

# device scopes, for the planner's and the layout's shares of the step
PLAN_SCOPE = "sgns.plan"        # negative replay, block planner, metadata
LAYOUT_SCOPE = "sgns.layout"    # (V, d) <-> (V, 1, dp), when the trainer converts

# the SGNS Mosaic kernel, read by ``sgns_kernel_roofline``,
# ``step_nonkernel_share`` and ``kernel_dma_bw_share``
SGNS_KERNEL = "sgns_hbm_pipe"

# counters, read by ``rows_moved_per_pair``, ``kernel_dma_bw_share``
# and ``hot_row_share``
ROWS_MOVED = "sgns.rows_moved"    # table rows the kernel moved HBM<->VMEM
BYTES_MOVED = "sgns.bytes_moved"  # the same rows in bytes
PAIRS = "sgns.pairs"              # skip-gram pairs trained
HOT_TOUCHES = "sgns.hot_touches"  # row references (centre, context,
                                  # negatives) to the hot tier's rows
# the counters a training step computes on the device, beside its loss
STEP_COUNTERS = (ROWS_MOVED, HOT_TOUCHES)

# the trainer's conversions of its tables between the (n, V, d) layout
# and the engine's packed one, each of a whole set of tables; no
# metric reads it yet
RELAYOUTS = "train.relayouts"

# counters of the graph walk source, read by ``walk_host_share``
WALK_NS = "graph.walk_ns"         # host ns inside ``repro.ingest.walk``
GRAPH_PAIRS = "graph.pairs"       # pairs the walk source cut

FOLD_EVERY = 256   # device values kept per counter before a device-side sum


def span(name: str, **ids):
    """A host span named ``name`` carrying ``ids`` (ints) as its stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


@jax.jit
def _fold(acc, values):
    """``acc`` (hi, lo) plus the sum of ``values`` (non-negative int32),
    in two int32 limbs, ``hi·2**16 + lo``, so no sum overflows."""
    lo = acc[1] + jnp.sum(values & 0xFFFF)
    hi = acc[0] + jnp.sum(values >> 16) + (lo >> 16)
    return jnp.stack([hi, lo & 0xFFFF])


def _to_int(x) -> int:
    """The sum of a device array's elements, read back (one copy of each
    shard, on this process's devices)."""
    return sum(int(np.asarray(s.data, dtype=np.int64).sum())
               for s in x.addressable_shards if s.replica_id == 0)


_lock = threading.Lock()
_host: dict[str, int] = {}
_pending: dict[tuple[str, int], list] = {}     # (name, times) → arrays
_sums: dict[tuple[str, int, object], jax.Array] = {}   # + device → (hi, lo)


def count(name: str, value, times: int = 1) -> None:
    """Add ``value × times`` to counter ``name``. ``value`` is a Python
    int or a device array of non-negative int32 (all its elements
    count); a device array is kept by reference."""
    with _lock:
        if not isinstance(value, jax.Array):
            _host[name] = _host.get(name, 0) + int(value) * times
            return
        pending = _pending.setdefault((name, times), [])
        pending.append(value)
        if len(pending) >= FOLD_EVERY:
            _fold_pending(name, times, pending)
            pending.clear()


def _fold_pending(name: str, times: int, values: list) -> None:
    by_device: dict = {}
    for v in values:
        for s in v.addressable_shards:
            if s.replica_id == 0:
                by_device.setdefault(s.device, []).append(
                    jnp.ravel(s.data).astype(jnp.int32))
    for dev, parts in by_device.items():
        key = (name, times, dev)
        acc = _sums.get(key)
        if acc is None:
            acc = jax.device_put(np.zeros(2, np.int32), dev)
        _sums[key] = _fold(acc, jnp.concatenate(parts))


def read(name: str) -> int:
    """Counter ``name`` (0 if never counted); reads device values back."""
    with _lock:
        total = _host.get(name, 0)
        for (n, times), values in _pending.items():
            if n == name:
                total += times * sum(_to_int(v) for v in values)
        for (n, times, _), acc in _sums.items():
            if n == name:
                hi, lo = np.asarray(acc, dtype=np.int64)
                total += times * (int(hi) * 2 ** 16 + int(lo))
        return total


def reset() -> None:
    """Forget every counter."""
    with _lock:
        _host.clear()
        _pending.clear()
        _sums.clear()
