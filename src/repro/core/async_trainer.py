"""The Train phase — zero-collective asynchronous sub-model training.

The paper's reducers each train one SGNS sub-model with **no parameter
synchronization whatsoever**. On a TPU mesh this maps to a ``worker``
mesh axis: stacked sub-model tables ``(n, V, d)`` are sharded over
``worker`` and the epoch function runs under ``shard_map`` with *no
collective anywhere in the step* — asserted by
:func:`assert_no_collectives`, and visible as a zero collective-bytes
roofline term (EXPERIMENTS §Roofline).

The per-step compute itself (negative draw → row grads → apply) is an
:class:`repro.core.engine.UpdateEngine`; every epoch builder here takes
``engine=`` and stays agnostic to which step path (dense autodiff,
sparse scatter-add, Pallas tile kernel, the fully-fused in-kernel
sampler, its HBM-blocked paper-scale variant, or the double-buffered
DMA-pipelined variant) runs inside the scan.

The synchronized strawman (`sync_train_epoch`) is conventional
data-parallel SGNS: one table, batch sharded, gradient all-reduced every
step — the TPU-native equivalent of the paper's Hogwild/MLLib baselines.

The ``vmap`` backend runs every worker on one device; the ``shard_map``
backend shards them over a ``worker`` mesh of every device (one host
with several chips, several hosts, or the dry-run's simulated mesh).
"""

from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass, field
from functools import cached_property, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

from repro import obs
from repro.core import sgns
from repro.core.engine import UpdateEngine, get_engine
from repro.core.sgns import SGNSConfig

# ---------------------------------------------------------------------------
# Single-worker epoch: scan over a fixed number of steps.
# ---------------------------------------------------------------------------
def make_worker_epoch(cfg: SGNSConfig, total_steps: int, engine="sparse"):
    """Returns epoch_fn(tables, centers (S,B), contexts (S,B), neg_table,
    key, step0) -> (tables, losses (S,), counts): ``tables`` are the
    worker's W and C in the engine's packed layout (``engine.pack`` of
    each ``(V, d)`` table), taken and returned as the scan carries them;
    ``counts`` maps each name of ``repro.obs.STEP_COUNTERS`` to its
    int32 sum over the S steps (the table rows the engine's kernel
    moved, the row references to its hot tier).

    ``engine`` (an :class:`repro.core.engine.UpdateEngine` or spec
    string) owns the whole per-step compute: negative draw, row grads,
    parameter apply. ``neg_table`` is the worker's *own* unigram^0.75
    noise table in the layout ``engine.table_kind`` names — a ``(V,)``
    CDF or a ``{'prob', 'alias'}`` Vose table (each sub-model draws from
    its own sample's noise distribution, paper §3.2).
    """
    engine = get_engine(engine)
    step = engine.make_packed_step(cfg, total_steps)

    def epoch_fn(tables, centers, contexts, neg_table, key, step0):
        def body(carry, xs):
            tables, key, i, totals = carry
            c_b, x_b = xs
            key, sub = jax.random.split(key)
            tables, loss, counts = step(tables, c_b, x_b, neg_table, sub,
                                        step0 + i)
            totals = {n: totals[n] + counts[n] for n in totals}
            return (tables, key, i + 1, totals), loss

        zeros = {n: jnp.int32(0) for n in obs.STEP_COUNTERS}
        (tables, _, _, totals), losses = jax.lax.scan(
            body, (tables, key, jnp.int32(0), zeros), (centers, contexts))
        return tables, losses, totals

    return epoch_fn


def converting(epoch_fn, engine: UpdateEngine, cfg: SGNSConfig):
    """``epoch_fn`` of :func:`make_worker_epoch` on ``(V, d)`` tables:
    packed before the scan and unpacked after it, inside one program."""
    def fn(params, *args):
        with jax.named_scope(obs.LAYOUT_SCOPE):
            tables = {k: engine.pack(v) for k, v in params.items()}
        tables, losses, counts = epoch_fn(tables, *args)
        with jax.named_scope(obs.LAYOUT_SCOPE):
            params = {k: engine.unpack(v, cfg) for k, v in tables.items()}
        return params, losses, counts

    return fn


# ---------------------------------------------------------------------------
# The trainer's tables, in the layout the engine's step carries
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TableLayout:
    """How a trainer's stacked ``(n, V, d)`` tables convert to and from
    its engine's packed layout: one small jitted program per direction,
    one table per call, under the layout scope (``repro.obs``)."""

    engine: UpdateEngine
    cfg: SGNSConfig
    sharding: NamedSharding | None = None   # the tables' (shard_map)

    @cached_property
    def converts(self) -> bool:
        """Whether the packed layout differs from ``(n, V, d)``, seen in
        the shape :meth:`UpdateEngine.pack` gives; where it does not,
        nothing is ever converted."""
        table = jax.ShapeDtypeStruct(
            (1, self.cfg.vocab_size, self.cfg.dim), jnp.float32)
        return jax.eval_shape(self.engine.pack, table).shape != table.shape

    @cached_property
    def pack(self):
        return self._jit(self.engine.pack)

    @cached_property
    def unpack(self):
        return self._jit(partial(self.engine.unpack, cfg=self.cfg))

    def _jit(self, fn):
        def convert(table):
            with jax.named_scope(obs.LAYOUT_SCOPE):
                return fn(table)

        if self.sharding is None:
            return jax.jit(convert)
        return jax.jit(convert, out_shardings=self.sharding)


@jax.tree_util.register_pytree_node_class
class Tables(Mapping):
    """Every worker's tables (``"W"``, ``"C"``), kept between chunks in
    the layout the engine's step carries, so that consecutive
    :meth:`AsyncShardTrainer.epoch` calls convert nothing.

    ``tables["W"]`` is the ``(n, V, d)`` table: the first read converts
    every table back, one at a time and each in place of its packed
    form, so that both layouts of all tables are never held at once;
    the next ``epoch`` packs them again. As a pytree its leaves are the
    arrays in the layout they are in now: ``jax.block_until_ready``
    waits for them and converts nothing. Each conversion of the whole
    set counts once in ``repro.obs.RELAYOUTS``."""

    def __init__(self, arrays, layout: TableLayout, packed: bool):
        self._arrays = dict(arrays)
        self._layout = layout
        self._packed = packed

    def __getitem__(self, name):
        return self._as(packed=False)[name]

    def __iter__(self):
        return iter(self._arrays)

    def __len__(self):
        return len(self._arrays)

    def packed(self) -> dict:
        """The arrays in the engine's packed layout, packed first if
        they are not."""
        return dict(self._as(packed=True))

    def _as(self, packed: bool) -> dict:
        if self._packed != packed and self._layout.converts:
            convert = self._layout.pack if packed else self._layout.unpack
            for name in list(self._arrays):
                # Wait for each conversion before dropping the table it
                # read: the next one then reuses its memory.
                self._arrays[name] = convert(
                    self._arrays[name]).block_until_ready()
            obs.count(obs.RELAYOUTS, 1)
        self._packed = packed
        return self._arrays

    def tree_flatten(self):
        return (tuple(self._arrays.values()),
                (tuple(self._arrays), self._layout, self._packed))

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, layout, packed = aux
        return cls(zip(names, children), layout, packed)


# ---------------------------------------------------------------------------
# Async (paper) trainer
# ---------------------------------------------------------------------------
@dataclass
class AsyncShardTrainer:
    """Trains n sub-models fully asynchronously.

    ``backend='vmap'``     — one device, workers vectorized (tests/CPU).
    ``backend='shard_map'`` — workers sharded over the ``worker`` mesh
    axis; the compiled step contains no collectives.
    ``engine`` — an :class:`repro.core.engine.UpdateEngine` or spec
    string (``"dense"`` / ``"sparse"`` / ``"pallas"`` /
    ``"pallas_fused"`` / ``"pallas_fused_hbm"`` /
    ``"pallas_fused_pipe"`` / ``"pallas_fused_tiered"``, optionally
    ``":cdf"`` / ``":alias"``) that owns the per-step compute; resolved
    once at construction.
    ``plan`` — optional :class:`repro.data.pipeline.HostShardPlan` for
    multi-host ingestion: this host feeds :meth:`device_chunk` only its
    own workers' extracted rows and the trainer assembles the global
    ``(n, ...)`` device arrays (zero inter-host parameter traffic — the
    only multi-host exchange is the input assembly itself).

    The trainer owns its tables' layout: :meth:`epoch` returns them as
    :class:`Tables` in the engine's packed layout and takes them back
    as they are, converting only when the tables are read (``layout``).
    """

    cfg: SGNSConfig
    num_workers: int
    total_steps: int
    backend: str = "vmap"
    mesh: Mesh | None = None
    engine: object = "sparse"
    plan: object = None
    _jitted: object = field(default=None, init=False, repr=False, compare=False)
    _jitted_single: object = field(default=None, init=False, repr=False,
                                   compare=False)
    layout: TableLayout = field(default=None, init=False, repr=False,
                                compare=False)

    def __post_init__(self):
        self.engine = get_engine(self.engine)
        self.engine.validate(vocab_size=self.cfg.vocab_size)
        sharding = (NamedSharding(self.mesh, P("worker"))
                    if self.backend == "shard_map" and self.mesh is not None
                    else None)
        self.layout = TableLayout(self.engine, self.cfg, sharding)
        if self.plan is not None:
            if self.plan.num_workers != self.num_workers:
                raise ValueError(
                    f"plan covers {self.plan.num_workers} workers, "
                    f"trainer has {self.num_workers}")
            if self.plan.process_count > 1 and (
                    self.backend != "shard_map" or self.mesh is None):
                raise ValueError(
                    "multi-host ingestion needs backend='shard_map' "
                    "and a mesh")

    def init(self, key: jax.Array) -> dict:
        keys = jax.random.split(key, self.num_workers)
        fn = jax.vmap(lambda k: sgns.init_params(k, self.cfg))
        if self.backend == "shard_map" and self.mesh is not None:
            # Worker-sharded global tables from the start: on a
            # multi-process runtime the epoch's shard_map inputs must
            # already be global arrays (host-local default placement
            # cannot be resharded across processes implicitly).
            sh = NamedSharding(self.mesh, P("worker"))
            fn = jax.jit(fn, out_shardings={"W": sh, "C": sh})
        return fn(keys)

    def _epoch_fn(self):
        return make_worker_epoch(self.cfg, self.total_steps,
                                 engine=self.engine)

    def _sharded(self, epoch_fn):
        spec = P("worker")
        return jax.shard_map(
            jax.vmap(epoch_fn),  # local worker block (n/devices per device)
            mesh=self.mesh, check_vma=False,
            # spec is a pytree prefix, so the alias table's {prob, alias}
            # leaves pick up the worker sharding too.
            in_specs=(spec,) * 6,
            out_specs=(spec,) * 3,
        )

    def _jit_epoch(self):
        """Build + jit the epoch once; chunked streaming calls it many
        times per epoch, so the jit cache must live on the trainer. The
        stacked tables are donated: the epoch's result replaces them, and
        a second copy of every worker's tables would not fit one chip
        at the paper's shape."""
        if self._jitted is None:
            epoch_fn = self._epoch_fn()
            if self.backend == "vmap":
                fn = jax.vmap(epoch_fn)
            elif self.backend == "shard_map":
                assert self.mesh is not None
                fn = self._sharded(epoch_fn)
            else:
                raise ValueError(self.backend)
            object.__setattr__(self, "_jitted", jax.jit(fn, donate_argnums=0))
        return self._jitted

    def device_chunk(self, centers, contexts):
        """Host-local ``(num_local, S, B)`` chunk blocks → global
        ``(n, S, B)`` device arrays (worker-sharded under a plan+mesh;
        a plain transfer otherwise)."""
        if self.plan is None or self.mesh is None:
            return jnp.asarray(centers), jnp.asarray(contexts)
        from repro.launch.mesh import assemble_worker_array

        return (assemble_worker_array(self.mesh, self.plan, centers),
                assemble_worker_array(self.mesh, self.plan, contexts))

    def device_table(self, neg_table):
        """Global worker-sharded noise table from this host's local
        rows (pytree of ``(num_local, V)`` leaves under a plan+mesh;
        passthrough otherwise)."""
        if self.plan is None or self.mesh is None:
            return neg_table
        from repro.launch.mesh import assemble_worker_array

        return jax.tree.map(
            lambda a: assemble_worker_array(self.mesh, self.plan, a),
            neg_table)

    def epoch(self, params, centers, contexts, neg_table, key, step0=0):
        """params: the ``{"W", "C"}`` (n,V,d) tables, or the
        :class:`Tables` a previous call returned; centers/contexts:
        (n,S,B); neg_table: (n,V) CDF or {'prob','alias'} of (n,V) alias
        tables. Returns ``(tables, losses)``, the tables as
        :class:`Tables` in the engine's packed layout (packed here only
        if ``params`` were not); counts the chunk's pairs, the rows (and
        bytes) its kernel moved and its references to hot rows
        (``repro.obs``), read back only when a counter is read."""
        ids = {"step0": step0} if isinstance(step0, int) else {}
        with obs.span(obs.TRAIN_DISPATCH, **ids):
            if not isinstance(params, Tables):
                params = Tables(params, self.layout, packed=False)
            keys = jax.random.split(key, self.num_workers)
            step0 = jnp.full((self.num_workers,), step0, dtype=jnp.int32)
            tables, losses, counts = self._jit_epoch()(
                params.packed(), centers, contexts, neg_table, keys, step0)
            params = Tables(tables, self.layout, packed=True)
            obs.count(obs.PAIRS, centers.size)
            for name, value in counts.items():
                obs.count(name, value)
            obs.count(obs.BYTES_MOVED, counts[obs.ROWS_MOVED],
                      times=self.engine.row_bytes(self.cfg))
        return params, losses

    def worker_epoch(self, params, centers, contexts, neg_table, key, step0=0):
        """One worker's chunk, un-vmapped: params (V,d) pytree;
        centers/contexts (S,B); neg_table the worker's own (V,) CDF or
        {'prob','alias'} pair; ``key`` the exact per-(worker, chunk) key
        the stacked epoch would have split out for it
        (:func:`repro.core.driver.worker_chunk_key`).

        This is the elastic-training path: because every worker runs the
        same single-worker jit regardless of which host executes it or
        how many peers are alive, kill/resume/steal schedules are
        bit-identical to the uninterrupted elastic run by construction
        (vmapped and un-vmapped executions of the same program are *not*
        guaranteed bit-identical, so elasticity equivalence is defined
        against this path, not against :meth:`epoch`)."""
        if self._jitted_single is None:
            object.__setattr__(self, "_jitted_single", jax.jit(
                converting(self._epoch_fn(), self.engine, self.cfg)))
        params, losses, _ = self._jitted_single(
            params, centers, contexts, neg_table, key, jnp.int32(step0))
        return params, losses

    def lower_epoch(self, steps: int, batch: int):
        """Lower the sharded epoch for the dry-run, ShapeDtypeStruct only
        (the tables in the engine's packed layout, as :meth:`epoch`
        passes them)."""
        assert self.mesh is not None
        n, V, d = self.num_workers, self.cfg.vocab_size, self.cfg.dim
        spec = P("worker")
        sh = lambda s, t: jax.ShapeDtypeStruct(
            s, t, sharding=NamedSharding(self.mesh, spec))
        if self.engine.table_kind == "alias":
            neg = {"prob": sh((n, V), jnp.float32), "alias": sh((n, V), jnp.int32)}
        else:
            neg = sh((n, V), jnp.float32)       # per-worker negative CDFs
        table = jax.eval_shape(self.engine.pack,
                               jax.ShapeDtypeStruct((n, V, d), jnp.float32))
        params = {k: sh(table.shape, table.dtype) for k in ("W", "C")}
        args = (
            params,
            sh((n, steps, batch), jnp.int32),   # centers
            sh((n, steps, batch), jnp.int32),   # contexts
            neg,                                # per-worker noise tables
            sh((n, 2), jnp.uint32),             # PRNG keys
            sh((n,), jnp.int32),                # step0
        )
        fn = self._sharded(self._epoch_fn())
        return jax.jit(fn).lower(*args)


# ---------------------------------------------------------------------------
# Synchronized baseline (Hogwild/MLLib stand-in): data-parallel + all-reduce
# ---------------------------------------------------------------------------
def make_sync_epoch(cfg: SGNSConfig, neg_table, total_steps: int,
                    mesh: Mesh | None = None, data_axis: str = "worker",
                    engine="dense"):
    """One shared table; per-step gradient synchronization.

    Under a mesh, the batch is sharded over ``data_axis`` and the dense
    gradient is psum'd — the per-step collective the paper eliminates.
    The gradient must materialize densely for that all-reduce, so only
    the ``engine``'s negative draw and table layout are used here (its
    apply path is irrelevant to the baseline's cost model).
    """
    engine = get_engine(engine)

    def sample_negatives(key, shape):
        return engine.sample(neg_table, key, shape)

    def step(params, c_b, x_b, key, i):
        negs = sample_negatives(key, (c_b.shape[0], cfg.negatives))
        lr = sgns.linear_lr(i, total_steps, cfg)
        sum_loss, grads = jax.value_and_grad(sgns.sum_loss_fn)(params, c_b, x_b, negs)
        loss = sum_loss / c_b.shape[0]
        if mesh is not None:
            # Per-step synchronization: the collective the paper removes.
            grads = jax.tree.map(partial(jax.lax.psum, axis_name=data_axis), grads)
            loss = jax.lax.pmean(loss, axis_name=data_axis)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    def epoch_fn(params, centers, contexts, key, step0):
        def body(carry, xs):
            params, key, i = carry
            key, sub = jax.random.split(key)
            params, loss = step(params, xs[0], xs[1], sub, step0 + i)
            return (params, key, i + 1), loss
        (params, _, _), losses = jax.lax.scan(
            body, (params, key, jnp.int32(0)), (centers, contexts))
        return params, losses

    if mesh is None:
        return jax.jit(epoch_fn)

    return jax.jit(jax.shard_map(
        epoch_fn, mesh=mesh, check_vma=False,
        in_specs=(P(), P(None, data_axis), P(None, data_axis), P(), P()),
        out_specs=(P(), P())))


# ---------------------------------------------------------------------------
# Beyond-paper: periodic-sync (local-SGD) SGNS — interpolates between the
# per-step-synchronized baseline (k=1) and the paper's fully-asynchronous
# training (k→∞, with the final ALiR merge as the one-time "sync").
# Collective bytes scale as 1/k (EXPERIMENTS §Perf SGNS iterations).
# ---------------------------------------------------------------------------
def make_periodic_sync_epoch(cfg: SGNSConfig, neg_table,
                             total_steps: int, sync_every: int,
                             mesh: Mesh, data_axis: str = "worker",
                             engine="dense"):
    """One shared table; parameters are *averaged* across workers every
    ``sync_every`` steps (local SGD) instead of gradients every step.
    Between syncs each worker runs the ``engine``'s step unmodified —
    local SGD composes with any update engine."""
    engine_step = get_engine(engine).make_step(cfg, total_steps)

    def local_step(params, c_b, x_b, key, i):
        return engine_step(params, c_b, x_b, neg_table, key, i)

    def epoch_fn(params, centers, contexts, key, step0):
        # centers/contexts: (outer, sync_every, B_local)
        def outer_body(carry, xs):
            params, key, i = carry
            c_o, x_o = xs

            def inner_body(c2, xs2):
                params2, key2, i2 = c2
                key2, sub = jax.random.split(key2)
                params2, loss = local_step(params2, xs2[0], xs2[1], sub, i2)
                return (params2, key2, i2 + 1), loss

            (params, key, i), losses = jax.lax.scan(
                inner_body, (params, key, i), (c_o, x_o))
            # the periodic synchronization: average parameters
            params = jax.tree.map(
                partial(jax.lax.pmean, axis_name=data_axis), params)
            return (params, key, i), losses

        (params, _, _), losses = jax.lax.scan(
            outer_body, (params, key, step0), (centers, contexts))
        return params, jax.lax.pmean(losses, axis_name=data_axis)

    spec_b = P(None, None, data_axis)
    return jax.jit(jax.shard_map(
        epoch_fn, mesh=mesh, check_vma=False,
        in_specs=(P(), spec_b, spec_b, P(), P()),
        out_specs=(P(), P())))


# ---------------------------------------------------------------------------
def assert_no_collectives(lowered) -> str:
    """Raises if the lowered/compiled program contains any cross-device
    collective — the paper's headline property for the train phase.
    Delegates to the structured op-walk in
    :mod:`repro.analysis.contracts`, which understands both StableHLO
    MLIR (``stablehlo.all_reduce`` — what ``.as_text()`` yields on a
    ``Lowered``) and post-compile HLO (``all-reduce``); the old
    hyphen-spelling regex was vacuous on the MLIR form."""
    from repro.analysis.contracts import certify_zero_collective

    return certify_zero_collective(lowered)


def count_collective_ops(hlo_text: str) -> dict[str, int]:
    """Collective ops by name in either program format (structured
    parse via :mod:`repro.analysis.contracts`)."""
    from repro.analysis import contracts

    return contracts.count_collective_ops(hlo_text)
