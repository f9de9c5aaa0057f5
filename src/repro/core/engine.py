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
``pallas_fused``
    The whole step in one Pallas kernel
    (``kernels/sgns_fused.py``): negatives drawn *in-kernel* from the
    alias tables via a counter-based PRNG, ``log σ`` forward + all three
    row grads + scatter-add apply in a single VMEM pass. Negative ids
    and the ``(B, K)`` logit/grad intermediates never touch HBM. Both
    ``(V, d)`` tables ride through the kernel whole, so this caps at
    VMEM-adjacent table sizes.
``pallas_fused_hbm``
    The fused step with **HBM-resident** tables
    (``kernels/sgns_fused_hbm.py``): a chain of per-block kernel
    invocations (tables aliased in place through every one) DMA-gathers
    / RMW-scatters only each ``block_pairs``-sized block's touched
    rows; negatives still drawn in-kernel from the (VMEM-resident)
    alias tables with the same replayable counter PRNG. This is the
    variant that reaches the paper's 300k×500 sub-model shape. Fields:
    ``block_pairs`` (a shorter tail block covers any remainder) and
    ``sequential`` (word2vec's true per-pair apply order instead of
    per-block).
``pallas_fused_pipe``
    The pipelined successor of ``pallas_fused_hbm``
    (``kernels/sgns_fused_pipe.py``): one kernel invocation per step, a
    ``ring_depth``-slot ring of VMEM row buffers (default 2) with
    per-slot DMA semaphores, and a pure-JAX block planner that dedups
    each block's touched rows (each row moves over DMA exactly once per
    block, no RMW round-trips) and flags the scatter-before-regather
    hazards the schedule serializes on. Bit-identical to
    ``pallas_fused_hbm`` — same replayed counter PRNG, same per-block
    chain semantics. ``sequential=True`` is served by the unpipelined
    kernel (per-pair order is inherently serial).
``pallas_fused_tiered``
    The pipelined engine with **frequency-tiered parameter placement**
    (``kernels/sgns_fused_tiered.py``): the ``hot_rows`` hottest rows
    by unigram count — the id prefix, since the vocab is
    frequency-sorted — live in a VMEM-resident copy of the table
    prefix (bulk-DMA'd in once per step and written back once), while
    cold rows stay HBM-resident behind the same DMA pipeline (dedup
    and hazards computed over cold rows only). A tunable dial on the
    VMEM-vs-HBM cliff: ``hot_rows=0`` is ``pallas_fused_pipe``,
    ``hot_rows=V`` is pure-resident like ``pallas_fused``. Bit-identical
    to ``pallas_fused_hbm`` at every setting.

Engine specs are engine instances or strings, optionally carrying a
sampler: ``"sparse"``, ``"sparse:alias"``, ``"pallas:cdf"``. The fused
engines always sample in-kernel from alias tables (``"alias"`` is their
only valid sampler, and their default).

Every Pallas engine takes ``interpret=None`` (Mosaic on a TPU, the
interpreter elsewhere). Under Mosaic, ``pallas``, ``pallas_fused_pipe``
and ``pallas_fused_tiered`` compile; ``pallas_fused`` and
``pallas_fused_hbm`` (and so ``sequential=True``) raise when their step
is built, naming the missing lowering.

Engines are frozen dataclasses, so they hash/compare by value and are
safe as jit static arguments or cache keys.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from repro.core import sgns
from repro.core.sgns import SGNSConfig
from repro.data.pairs import negative_sampler_fn
from repro.kernels.mode import require_interpret, resolve_interpret


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

    def pack(self, params: dict) -> dict:
        """``(V, d)`` tables → the layout :meth:`make_packed_step`'s
        step carries (identity unless a kernel needs its own layout)."""
        return params

    def unpack(self, packed: dict, cfg: SGNSConfig) -> dict:
        """Inverse of :meth:`pack`."""
        return packed

    def make_packed_step(self, cfg: SGNSConfig, total_steps: int):
        """:meth:`make_step` on packed tables — what a scan of steps
        carries, so that the layout is converted once per scan. Its step
        returns ``(params, mean_loss, rows)``: ``rows`` (int32) counts
        the table rows the step's kernel moves between HBM and VMEM by
        DMA, 0 where no kernel moves rows by DMA."""
        return _no_rows(self.make_step(cfg, total_steps))

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


def _no_rows(step):
    """``step`` with a count of 0 DMA-moved rows beside its loss."""
    def packed(*args):
        params, loss = step(*args)
        return params, loss, jnp.int32(0)

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
class FusedPallasEngine(UpdateEngine):
    """One kernel per step: in-kernel alias negative sampling + forward
    + row grads + apply. Alias tables only."""

    sampler: str = "alias"
    interpret: bool | None = None
    name = "pallas_fused"

    def __post_init__(self):
        if self.sampler != "alias":
            raise ValueError(
                f"{self.name} samples in-kernel from alias tables; "
                f"sampler {self.sampler!r} is not supported")

    def sample(self, table, key, shape):
        """Replay the kernel's counter-PRNG draw outside the kernel
        (exactly the ids an in-kernel step with this key draws)."""
        from repro.kernels.sgns_fused import fused_negative_ids, _as_seed

        return fused_negative_ids(_as_seed(key), table["prob"],
                                  table["alias"], shape)

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """Single-kernel step: in-kernel draw + forward + row grads +
        apply, both tables VMEM-resident."""
        from repro.kernels.sgns_fused import NO_MOSAIC, sgns_fused_step

        interpret = require_interpret(self.interpret, self.name, NO_MOSAIC)

        def step(params, centers, contexts, neg_table, key, step_idx):
            lr = sgns.linear_lr(step_idx, total_steps, cfg)
            return sgns_fused_step(params, centers, contexts, neg_table, key,
                                   lr, negatives=cfg.negatives,
                                   interpret=interpret)

        return step


@dataclass(frozen=True)
class FusedHBMPallasEngine(FusedPallasEngine):
    """The fused step against HBM-resident ``(V, d)`` tables: a chain
    of per-block kernel invocations, each DMA-gathering/scattering only
    the touched rows, with the in-kernel alias draw (same counter PRNG
    ⇒ same replay). Reaches the paper's 300k×500 sub-model shape the
    VMEM-resident variant cannot.

    ``block_pairs`` — pairs per block invocation (a shorter tail block
    covers any batch remainder).
    ``sequential``  — word2vec's true per-pair sequential apply (each
    pair's grads see every earlier pair's updates) instead of the
    default per-block semantics. Slower; the update-order oracle.
    """

    block_pairs: int = 256
    sequential: bool = False
    name = "pallas_fused_hbm"

    def __post_init__(self):
        super().__post_init__()
        if self.block_pairs < 1:
            raise ValueError(
                f"{self.name} needs block_pairs >= 1 (pairs per kernel "
                f"block), got {self.block_pairs}")

    def make_step(self, cfg: SGNSConfig, total_steps: int):
        """Per-block kernel-chain step against HBM-resident tables
        (DMA gather/RMW-scatter of touched rows only)."""
        from repro.kernels.sgns_fused_hbm import (
            NO_MOSAIC, sgns_fused_hbm_step)

        interpret = require_interpret(self.interpret, self.name, NO_MOSAIC)

        def step(params, centers, contexts, neg_table, key, step_idx):
            lr = sgns.linear_lr(step_idx, total_steps, cfg)
            return sgns_fused_hbm_step(
                params, centers, contexts, neg_table, key, lr,
                negatives=cfg.negatives, block_pairs=self.block_pairs,
                sequential=self.sequential, interpret=interpret)

        return step


@dataclass(frozen=True)
class FusedPipePallasEngine(FusedHBMPallasEngine):
    """The HBM-resident fused step with the **double-buffered DMA
    pipeline** (``kernels/sgns_fused_pipe.py``): a single kernel
    invocation per step in which block *i+1*'s deduped row gathers are
    in flight while block *i* computes and block *i-1*'s write-backs
    drain, hazard-ordered by the pure-JAX block planner. Bit-identical
    to ``pallas_fused_hbm`` (same replayed counter-PRNG negatives, same
    per-block chain semantics) with strictly less HBM traffic — each
    touched row moves exactly once per block in each direction.

    ``block_pairs`` — pairs per pipeline block (the batch is padded to
    whole blocks; padded pairs are masked to exactly-zero updates).
    ``ring_depth`` — VMEM row-buffer ring slots (≥ 2): a deeper ring
    keeps more blocks' write-backs in flight before the slot-recycling
    wait, at ``ring_depth × block_pairs × (K+2) × d`` floats of VMEM.
    ``sequential`` — word2vec's per-pair apply order is inherently
    unpipelineable, so ``sequential=True`` transparently runs the
    unpipelined :func:`~repro.kernels.sgns_fused_hbm.sgns_fused_hbm_step`
    oracle path instead.
    """

    ring_depth: int = 2
    name = "pallas_fused_pipe"

    def __post_init__(self):
        super().__post_init__()
        if self.ring_depth < 2:
            raise ValueError(
                f"{self.name} needs ring_depth >= 2 (gathers of block "
                f"b+1 must overlap scatters of block b), got "
                f"{self.ring_depth}")

    hot_rows = 0    # the tiered engine's field; the pure pipeline has none

    def pack(self, params):
        """Tables → the kernels' ``(V, 1, dp)`` row layout
        (:func:`repro.kernels.sgns_fused_pipe.to_row_layout`)."""
        if self.sequential:
            return params
        from repro.kernels.sgns_fused_pipe import to_row_layout

        interpret = resolve_interpret(self.interpret)
        return {k: to_row_layout(v, interpret) for k, v in params.items()}

    def unpack(self, packed, cfg):
        if self.sequential:
            return packed
        from repro.kernels.sgns_fused_pipe import from_row_layout

        return {k: from_row_layout(v, cfg.dim) for k, v in packed.items()}

    def make_packed_step(self, cfg: SGNSConfig, total_steps: int):
        """One pipelined-kernel step on row-layout tables (multi-slot DMA
        ring, deduped row traffic, the hot tier when ``hot_rows > 0``),
        counting the rows its kernel moves; ``sequential=True`` falls
        back to the HBM oracle, whose rows are not counted."""
        if self.sequential:
            return _no_rows(FusedHBMPallasEngine.make_step(
                self, cfg, total_steps))
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
            packed, loss, _ = packed_step(self.pack(params), *args)
            return self.unpack(packed, cfg), loss

        return step

    def row_bytes(self, cfg: SGNSConfig) -> int:
        """Bytes of one ``(1, dp)`` row of the kernels' layout."""
        from repro.kernels.sgns_fused_pipe import row_width

        return 4 * row_width(cfg.dim, resolve_interpret(self.interpret))


@dataclass(frozen=True)
class FusedTieredPallasEngine(FusedPipePallasEngine):
    """The pipelined HBM engine with **frequency-tiered hot/cold
    parameter placement** (``kernels/sgns_fused_tiered.py``): the
    ``hot_rows`` hottest rows by unigram count — the id prefix, since
    ``build_vocab`` sorts ids by descending frequency — are pinned in a
    VMEM-resident copy of each table's prefix (one bulk DMA in at step
    start, one back at step end), while cold rows stay HBM-resident
    behind the inherited ``ring_depth``-slot DMA pipeline with dedup
    and hazard flags computed over cold rows only. Under Zipfian word
    frequencies the hot prefix absorbs most row traffic, so per-block
    DMA volume collapses while the VMEM footprint stays a chosen
    ``2 × hot_rows × d`` floats — a tunable dial from pure-pipe
    (``hot_rows=0``, delegates to the pipelined kernel) to
    pure-resident (``hot_rows ≥ V``, zero per-block row DMAs).
    Bit-identical to ``pallas_fused_hbm`` at every setting.

    ``hot_rows`` — rows pinned per table. Must be ≥ 0; the trainer
    rejects ``hot_rows > V`` at construction (:meth:`validate`) — a
    hot tier larger than the vocabulary is a misconfiguration, not a
    request for pure-resident placement (use ``hot_rows = V`` for
    that; direct kernel calls still clamp).
    ``block_pairs`` / ``ring_depth`` / ``sequential`` — as inherited
    (``sequential=True`` falls back to the unpipelined oracle, which is
    tier-free but bit-identical anyway).
    """

    hot_rows: int = 256
    name = "pallas_fused_tiered"

    def __post_init__(self):
        super().__post_init__()
        if self.hot_rows < 0:
            raise ValueError(
                f"{self.name} needs hot_rows >= 0, got {self.hot_rows}")

    def validate(self, *, vocab_size: int | None = None) -> None:
        """Reject a hot tier larger than the table it is a prefix of."""
        super().validate(vocab_size=vocab_size)
        if vocab_size and self.hot_rows > vocab_size:
            raise ValueError(
                f"{self.name} hot_rows={self.hot_rows} exceeds "
                f"vocab_size={vocab_size}; the hot tier is a prefix of "
                f"the (V, d) table — use hot_rows <= V (hot_rows=V is "
                f"fully VMEM-resident)")


ENGINES: dict[str, type[UpdateEngine]] = {
    "dense": DenseEngine,
    "sparse": SparseEngine,
    "pallas": PallasEngine,
    "pallas_fused": FusedPallasEngine,
    "pallas_fused_hbm": FusedHBMPallasEngine,
    "pallas_fused_pipe": FusedPipePallasEngine,
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
