"""Update engines — the per-step SGNS compute as one swappable object.

An :class:`UpdateEngine` owns everything a training step does between
receiving a ``(centers, contexts)`` micro-batch and returning updated
parameters: the negative draw (and therefore the noise-table *layout*
it consumes), the row gradients, and the parameter apply. Every layer
above — ``make_worker_epoch`` / :class:`AsyncShardTrainer` /
``make_sync_epoch`` / ``make_periodic_sync_epoch`` / the driver / the
launch CLIs — selects one by name instead of threading the old
``sparse`` / ``row_grad_fn`` / ``sampler`` flag trio.

Registry (``get_engine``):

``dense``
    Autodiff through the gathers; materializes a dense ``(V, d)``
    gradient. The oracle — simple and slow.
``sparse``
    Manual per-row gradients + accumulating scatter-add (pure jnp).
    O(B·K·d) memory traffic; the CPU production path.
``pallas``
    ``sparse`` with the row gradients computed by the VMEM-tile Pallas
    kernel (``kernels/sgns_update.py``); gather/scatter stay in XLA.
``pallas_fused_tiered``
    The whole step in one Pallas kernel against **HBM-resident** tables
    (``kernels/sgns_fused_pipe.py``), the engine the chip runs. A
    pure-JAX block planner replays the counter-PRNG alias draw
    (``kernels/alias_draw.py``), dedups each ``block_pairs``-sized
    block's touched rows and flags the scatter-before-regather hazards;
    the kernel streams the blocks through a ``ring_depth``-slot ring of
    VMEM row buffers, each touched row moving once per block in each
    direction. The ``hot_rows`` hottest rows — the id prefix, since the
    vocab is frequency-sorted — live in a VMEM-resident copy of the
    table prefix for the whole step. ``hot_rows = 0`` is the pure-DMA
    case, ``hot_rows = V`` the pure-resident one. Bit-identical at every
    setting to ``sparse`` run once per pair block on the replayed
    negatives.

Engine specs are engine instances or strings, optionally carrying a
sampler: ``"sparse"``, ``"sparse:alias"``, ``"pallas:cdf"``. The fused
engine samples from alias tables (``"alias"`` is its only valid
sampler, and its default).

Every Pallas engine takes ``interpret=None``: compiled by Mosaic on a
TPU, interpreted elsewhere.

Engines are frozen dataclasses, so they hash/compare by value and are
safe as jit static arguments or cache keys.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import sgns
from repro.core.sgns import SGNSConfig
from repro.data.pairs import negative_sampler_fn
from repro.kernels.mode import resolve_interpret


@dataclass(frozen=True)
class UpdateEngine:
    """Base engine: negative draw + step construction.

    ``sampler`` names the negative-draw primitive ("cdf" | "alias") and
    fixes :attr:`table_kind`, the noise-table layout the engine's steps
    consume — a ``(V,)`` CDF or a ``{"prob", "alias"}`` Vose table (see
    ``repro.data.pairs.build_noise_table``).
    """

    sampler: str = "cdf"
    name = "base"

    @property
    def table_kind(self) -> str:
        """Noise-table layout this engine's steps consume ("cdf" |
        "alias") — pass to ``build_noise_table(kind=...)``."""
        return self.sampler

    def sample(self, table, key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
        """Draw negative ids outside a kernel (also the sync baselines'
        draw path)."""
        return negative_sampler_fn(self.sampler)(table, key, shape)

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """Returns ``step(params, centers, contexts, neg_table, key,
        step_idx) -> (params, mean_loss)``."""
        raise NotImplementedError

    def pack(self, table: jax.Array) -> jax.Array:
        """A ``(..., V, d)`` table → the layout :meth:`make_packed_step`'s
        step carries (identity unless a kernel needs its own layout)."""
        return table

    def unpack(self, table: jax.Array, cfg: SGNSConfig) -> jax.Array:
        """Inverse of :meth:`pack`."""
        return table

    def make_packed_step(self, cfg: SGNSConfig, total_steps: int):
        """:meth:`make_step` on packed tables — what a scan of steps
        carries, so that the layout is converted outside it. Its step
        returns ``(params, mean_loss, counts)``: ``counts`` maps each
        name of ``repro.obs.STEP_COUNTERS`` to an int32 scalar, the
        table rows the step's kernel moves between HBM and VMEM by DMA
        and the row references to its hot tier, each 0 where the engine
        has no such kernel or tier."""
        return _no_counts(self.make_step(cfg, total_steps))

    def row_bytes(self, cfg: SGNSConfig) -> int:
        """Bytes of one table row as the packed step moves it."""
        return 4 * cfg.dim

    def validate(self, *, vocab_size: int | None = None) -> None:
        """Check dials that only make sense against a model shape
        (``__post_init__`` covers the shape-free ones). Called by
        :class:`~repro.core.async_trainer.AsyncShardTrainer` at
        construction; raises ``ValueError`` on a bad combination."""

    def describe(self) -> str:
        """Human-readable ``"name:sampler"`` tag (log/bench labels)."""
        return f"{self.name}:{self.sampler}"


def _no_counts(step):
    """``step`` with its counters, all 0, beside its loss."""
    def packed(*args):
        params, loss = step(*args)
        return params, loss, {n: jnp.int32(0) for n in obs.STEP_COUNTERS}

    return packed


@dataclass(frozen=True)
class DenseEngine(UpdateEngine):
    """Autodiff + dense (V, d) gradient — the numerical oracle."""

    name = "dense"

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """Autodiff step: ``value_and_grad`` through the gathers, dense
        ``(V, d)`` gradient, full-table SGD apply."""
        def step(params, centers, contexts, neg_table, key, step_idx):
            negs = self.sample(neg_table, key, (centers.shape[0], cfg.negatives))
            lr = sgns.linear_lr(step_idx, total_steps, cfg)
            sum_loss, grads = jax.value_and_grad(sgns.sum_loss_fn)(
                params, centers, contexts, negs)
            params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return params, sum_loss / centers.shape[0]

        return step


@dataclass(frozen=True)
class SparseEngine(UpdateEngine):
    """Manual row grads + scatter-add; ``row_grad_fn`` is the seam the
    Pallas engine plugs into."""

    name = "sparse"

    def row_grad_fn(self, cfg: SGNSConfig):
        """Per-row gradient callable the step threads into
        ``train_step_sparse`` (subclass hook — see PallasEngine)."""
        return sgns.sparse_row_grads

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """Sparse step: XLA draw + gather, manual row grads, per-row
        accumulating scatter-add apply."""
        row_grads = self.row_grad_fn(cfg)

        def step(params, centers, contexts, neg_table, key, step_idx):
            negs = self.sample(neg_table, key, (centers.shape[0], cfg.negatives))
            lr = sgns.linear_lr(step_idx, total_steps, cfg)
            return sgns.train_step_sparse(params, centers, contexts, negs, lr,
                                          row_grad_fn=row_grads)

        return step


@dataclass(frozen=True)
class PallasEngine(SparseEngine):
    """Sparse step with the fused-VMEM-tile row-grad kernel in the
    middle; the draw and the gather/scatter seams stay in XLA."""

    interpret: bool | None = None
    block_b: int | None = None
    name = "pallas"

    def row_grad_fn(self, cfg: SGNSConfig):
        """Swap the jnp row grads for the VMEM-tile Pallas kernel
        (interpret-mode off-TPU unless overridden)."""
        from repro.kernels import ops

        return ops.make_row_grad_fn(interpret=self.interpret,
                                    block_b=self.block_b)


@dataclass(frozen=True)
class FusedTieredPallasEngine(UpdateEngine):
    """The whole step in one Pallas kernel against HBM-resident tables
    (``kernels/sgns_fused_pipe.py``), with **frequency-tiered hot/cold
    parameter placement**.

    Each step replays the counter-PRNG alias draw
    (``kernels/alias_draw.py``) in a pure-JAX block planner, which
    dedups each ``block_pairs``-pair block's touched rows and flags the
    scatter-before-regather hazards. One kernel invocation then runs the
    blocks through a ``ring_depth``-slot ring of VMEM row buffers: block
    *i+1*'s row gathers are in flight while block *i* computes and older
    blocks' write-backs drain, and each touched row moves exactly once
    per block in each direction.

    The ``hot_rows`` hottest rows by unigram count — the id prefix,
    since ``build_vocab`` sorts ids by descending frequency — are pinned
    in a VMEM-resident copy of each table's prefix (one bulk DMA in at
    step start, one back at step end); cold rows ride the DMA ring, with
    dedup and hazard flags computed over cold rows only. ``hot_rows = 0``
    is the pure-DMA case (no hot tier), ``hot_rows = V`` the
    pure-resident one (no per-block row DMAs). Bit-identical at every
    setting to the per-block sparse reference on the replayed negatives.

    ``block_pairs`` — pairs per pipeline block (the batch is padded to
    whole blocks; padded pairs are masked to exactly-zero updates).
    ``ring_depth`` — VMEM row-buffer ring slots (≥ 2): a deeper ring
    keeps more blocks' write-backs in flight before the slot-recycling
    wait, at ``ring_depth × block_pairs × (K+2) × d`` floats of VMEM.
    ``hot_rows`` — rows pinned per table. Must be ≥ 0; the trainer
    rejects ``hot_rows > V`` at construction (:meth:`validate`) — a
    hot tier larger than the vocabulary is a misconfiguration, not a
    request for pure-resident placement (use ``hot_rows = V`` for
    that; direct kernel calls still clamp).
    """

    sampler: str = "alias"
    interpret: bool | None = None
    block_pairs: int = 256
    ring_depth: int = 2
    hot_rows: int = 256
    name = "pallas_fused_tiered"

    def __post_init__(self):
        if self.sampler != "alias":
            raise ValueError(
                f"{self.name} samples in-kernel from alias tables; "
                f"sampler {self.sampler!r} is not supported")
        if self.block_pairs < 1:
            raise ValueError(
                f"{self.name} needs block_pairs >= 1 (pairs per kernel "
                f"block), got {self.block_pairs}")
        if self.ring_depth < 2:
            raise ValueError(
                f"{self.name} needs ring_depth >= 2 (gathers of block "
                f"b+1 must overlap scatters of block b), got "
                f"{self.ring_depth}")
        if self.hot_rows < 0:
            raise ValueError(
                f"{self.name} needs hot_rows >= 0, got {self.hot_rows}")

    def validate(self, *, vocab_size: int | None = None) -> None:
        """Reject a hot tier larger than the table it is a prefix of."""
        if vocab_size and self.hot_rows > vocab_size:
            raise ValueError(
                f"{self.name} hot_rows={self.hot_rows} exceeds "
                f"vocab_size={vocab_size}; the hot tier is a prefix of "
                f"the (V, d) table — use hot_rows <= V (hot_rows=V is "
                f"fully VMEM-resident)")

    def sample(self, table, key, shape):
        """Replay the kernel's counter-PRNG draw outside the kernel
        (exactly the ids a step with this key draws)."""
        from repro.kernels.alias_draw import _as_seed, fused_negative_ids

        return fused_negative_ids(_as_seed(key), table["prob"],
                                  table["alias"], shape)

    def pack(self, table):
        """A table → the kernels' ``(..., V, 1, dp)`` row layout
        (:func:`repro.kernels.sgns_fused_pipe.to_row_layout`)."""
        from repro.kernels.sgns_fused_pipe import to_row_layout

        return to_row_layout(table, resolve_interpret(self.interpret))

    def unpack(self, table, cfg):
        from repro.kernels.sgns_fused_pipe import from_row_layout

        return from_row_layout(table, cfg.dim)

    def make_packed_step(self, cfg: SGNSConfig, total_steps: int):
        """One kernel step on row-layout tables (multi-slot DMA ring,
        deduped row traffic, the hot tier when ``hot_rows > 0``),
        counting the rows its kernel moves and its hot-tier
        references."""
        from repro.kernels.sgns_fused_pipe import fused_pipe_rows_step

        interpret = resolve_interpret(self.interpret)

        def step(tables, centers, contexts, neg_table, key, step_idx):
            lr = sgns.linear_lr(step_idx, total_steps, cfg)
            return fused_pipe_rows_step(
                tables, centers, contexts, neg_table, key, lr,
                negatives=cfg.negatives, block_pairs=self.block_pairs,
                ring_depth=self.ring_depth,
                hot_rows=min(self.hot_rows, tables["W"].shape[0]),
                interpret=interpret)

        return step

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """:meth:`make_packed_step` with the layout converted around
        each step."""
        packed_step = self.make_packed_step(cfg, total_steps)

        def step(params, *args):
            packed = {k: self.pack(v) for k, v in params.items()}
            packed, loss, _ = packed_step(packed, *args)
            return {k: self.unpack(v, cfg) for k, v in packed.items()}, loss

        return step

    def row_bytes(self, cfg: SGNSConfig) -> int:
        """Bytes of one ``(1, dp)`` row of the kernels' layout."""
        from repro.kernels.sgns_fused_pipe import row_width

        return 4 * row_width(cfg.dim, resolve_interpret(self.interpret))


ENGINES: dict[str, type[UpdateEngine]] = {
    "dense": DenseEngine,
    "sparse": SparseEngine,
    "pallas": PallasEngine,
    "pallas_fused_tiered": FusedTieredPallasEngine,
}
ENGINE_NAMES = tuple(ENGINES)


def get_engine(spec: str | UpdateEngine = "sparse", **overrides) -> UpdateEngine:
    """Resolve an engine spec: an instance (returned as-is, or with
    field overrides applied) or a ``"name"`` / ``"name:sampler"``
    string, e.g. ``get_engine("sparse:alias")``."""
    if isinstance(spec, UpdateEngine):
        return replace(spec, **overrides) if overrides else spec
    name, _, sampler = str(spec).partition(":")
    if name not in ENGINES:
        raise ValueError(
            f"unknown update engine {name!r}; expected one of "
            f"{sorted(ENGINES)} (optionally 'name:sampler')")
    if sampler:
        overrides.setdefault("sampler", sampler)
    return ENGINES[name](**overrides)
