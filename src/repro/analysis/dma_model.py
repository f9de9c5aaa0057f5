"""Bounded-exhaustive model checker for the fused-pipeline DMA schedule.

``kernels/sgns_fused_pipe.kernel_schedule`` is the static expansion of
the per-position template (``position_template``) that the kernel of
the ``pallas_fused_tiered`` engine loops over, at every ``hot_rows``:
an unrolled sequence of ``(op, block, slot, guard)`` events whose
guards are resolved against the planner's hazard flags. Its safety argument — matched start/wait
pairs under every hazard outcome, slot-recycling waits serializing
buffer reuse, a ``ring_depth - 1`` look-behind window sufficing for
chain fidelity — was previously exercised by hand-picked hazard
vectors. This module replaces that with bounded-exhaustive
verification:

* :func:`check_events` — a symbolic state machine over one resolved
  event sequence. It tracks per-slot in-flight DMAs, buffer ownership
  and per-block lifecycle counts, and reports a :class:`Violation` for
  every breach of the three safety properties:

  1. **matched DMAs** — every block's gather and scatter is started
     exactly once and waited exactly once, the wait follows its start
     on the same slot semaphore, and nothing is left in flight at the
     end of the step;
  2. **no slot rewrite under an in-flight DMA** — a gather may not
     overwrite a ring buffer whose previous occupant's write-back has
     not completed (the VMEM slot-reuse race class), and two DMAs of
     the same kind may never be in flight on one slot semaphore;
  3. **no WAR escape** — block *b*'s gathers may not issue while any
     older block that *may* share rows with it still has an undrained
     write-back. ``may_overlap(b0, b)`` is symbolic: inside the
     look-behind window it is exactly the planner's hazard flag;
     outside the window the planner proves nothing, so the checker
     demands the drain unconditionally — which is precisely the
     obligation the slot-recycling waits must discharge.

* :func:`check_schedule_space` — drives :func:`check_events` over
  every ``ring_depth`` × block count × hazard vector in the bound
  (the full space: ``resolve_schedule`` is pure Python, so
  exhausting it is cheap).

* :func:`check_planner` — closes the loop on the *flags themselves*:
  constructs concrete id streams realizing every bounded pattern of
  window overlaps (W-table / C-table / none, per window offset,
  including padded-tail batches and hot-tier routing), recomputes the
  expected hazards from the raw ids with an independent numpy oracle,
  asserts ``plan_blocks`` agrees plus the dedup/position-map
  invariants, then runs the resolved schedule through
  :func:`check_events` with ``may_overlap`` derived from the *actual*
  row sets — end-to-end: real ids → planner flags → schedule → chain
  fidelity.

The mutation tests in ``tests/test_analysis.py`` feed this checker
seeded defects (a dropped wait, a slot collision, an off-by-one hazard
window, a planner that zeroes its flags) and assert each is flagged —
a checker that cannot fail is not a check.

Standalone: ``python -m repro.analysis.dma_model [--max-nblocks N]``.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field

import numpy as np

from repro.kernels.sgns_fused_pipe import (
    DMA_WAIT_FOR_START,
    kernel_schedule,
    plan_blocks,
    resolve_schedule,
)

RING_DEPTHS = (2, 3, 4)


@dataclass(frozen=True)
class Violation:
    """One breach of a schedule safety property."""

    rule: str           # matched-dma | slot-race | sem-overlap | war-hazard | order
    detail: str
    ring_depth: int
    nblocks: int
    hazard: tuple[int, ...]

    def __str__(self) -> str:
        return (f"[{self.rule}] S={self.ring_depth} nblocks={self.nblocks} "
                f"hazard={list(self.hazard)}: {self.detail}")


@dataclass
class ModelCheckReport:
    """Aggregate result of a model-checking sweep."""

    schedules_checked: int = 0
    plans_checked: int = 0
    violations: list[Violation] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def merge(self, other: "ModelCheckReport") -> "ModelCheckReport":
        self.schedules_checked += other.schedules_checked
        self.plans_checked += other.plans_checked
        self.violations.extend(other.violations)
        return self

    def summary(self) -> str:
        head = (f"{self.schedules_checked} schedules, "
                f"{self.plans_checked} planner cases checked: "
                f"{'OK' if self.ok else f'{len(self.violations)} violation(s)'}")
        return "\n".join([head] + [f"  {v}" for v in self.violations[:20]])


def hazard_may_overlap(hazard, ring_depth: int):
    """The symbolic worst-case overlap relation consistent with a hazard
    vector: outside the look-behind window the planner proves nothing
    (every pair may overlap — the slot-recycling waits must cover it);
    inside the window the flag is the only information."""
    def may(b0: int, b: int) -> bool:
        if b - b0 >= ring_depth:
            return True
        return bool(hazard[b])
    return may


def check_events(events, nblocks: int, ring_depth: int, *,
                 may_overlap, hazard=(), expect_slot_policy: bool = True,
                 ) -> list[Violation]:
    """Simulate one resolved ``(op, block, slot)`` event sequence and
    return every safety violation (empty = certified for this vector).

    ``may_overlap(b0, b)`` (b0 < b) answers "may blocks b0 and b touch
    a common parameter row?" — the WAR obligation is only discharged
    for pairs where it returns False. ``expect_slot_policy`` also pins
    the ``slot == block % ring_depth`` assignment the ring implements
    (turn off to check foreign schedules that use another policy).
    """
    S = ring_depth
    hz = tuple(int(h) for h in hazard) or (0,) * nblocks
    out: list[Violation] = []

    def bad(rule: str, detail: str) -> None:
        out.append(Violation(rule, detail, S, nblocks, hz))

    started_g = [0] * nblocks
    waited_g = [0] * nblocks
    computed = [0] * nblocks
    started_s = [0] * nblocks
    waited_s = [0] * nblocks
    gather_inflight: dict[int, int] = {}    # slot -> block
    scatter_inflight: dict[int, int] = {}   # slot -> block
    buffer_owner: dict[int, int] = {}       # slot -> block computed into it
    next_compute = 0

    for op, b, s in events:
        if not (0 <= b < nblocks):
            bad("order", f"{op} references block {b} outside [0, {nblocks})")
            continue
        if expect_slot_policy and s != b % S:
            bad("order", f"{op} of block {b} on slot {s}, ring policy "
                         f"assigns slot {b % S}")
        if op == "gather":
            if started_g[b]:
                bad("matched-dma", f"gather of block {b} started twice")
            started_g[b] += 1
            if s in gather_inflight:
                bad("sem-overlap",
                    f"gather of block {b} starts on slot {s} while block "
                    f"{gather_inflight[s]}'s gather is in flight on the "
                    f"same semaphore")
            gather_inflight[s] = b
            p = buffer_owner.get(s)
            if p is not None:
                if not started_s[p]:
                    bad("slot-race",
                        f"gather of block {b} overwrites buf[{s}] before "
                        f"block {p}'s write-back even started")
                elif not waited_s[p]:
                    bad("slot-race",
                        f"gather of block {b} rewrites buf[{s}] while "
                        f"block {p}'s scatter DMA is in flight from it")
            for b0 in range(b):
                if may_overlap(b0, b) and not waited_s[b0]:
                    bad("war-hazard",
                        f"gather of block {b} issues while block {b0} "
                        f"(may share rows) has an undrained write-back")
        elif op == "wait_gather":
            if gather_inflight.get(s) != b:
                bad("matched-dma",
                    f"wait_gather of block {b} on slot {s} without a "
                    f"matching in-flight start "
                    f"(in flight: {gather_inflight.get(s)})")
            else:
                del gather_inflight[s]
            waited_g[b] += 1
        elif op == "compute":
            if waited_g[b] != 1:
                bad("order", f"compute of block {b} before its gather "
                             f"completed (waits seen: {waited_g[b]})")
            if computed[b]:
                bad("order", f"block {b} computed twice")
            if b != next_compute:
                bad("order", f"compute of block {b} out of chain order "
                             f"(expected block {next_compute})")
            next_compute = b + 1
            computed[b] += 1
            buffer_owner[s] = b
        elif op == "scatter":
            if not computed[b]:
                bad("order", f"scatter of block {b} before its compute")
            if buffer_owner.get(s) != b:
                bad("slot-race",
                    f"scatter of block {b} reads buf[{s}] now owned by "
                    f"block {buffer_owner.get(s)} (stale write-back)")
            if started_s[b]:
                bad("matched-dma", f"scatter of block {b} started twice")
            started_s[b] += 1
            if s in scatter_inflight:
                bad("sem-overlap",
                    f"scatter of block {b} starts on slot {s} while block "
                    f"{scatter_inflight[s]}'s scatter is in flight on the "
                    f"same semaphore")
            scatter_inflight[s] = b
        elif op == "wait_scatter":
            if scatter_inflight.get(s) != b:
                bad("matched-dma",
                    f"wait_scatter of block {b} on slot {s} without a "
                    f"matching in-flight start "
                    f"(in flight: {scatter_inflight.get(s)})")
            else:
                del scatter_inflight[s]
            waited_s[b] += 1
        else:
            bad("order", f"unknown op {op!r}")

    for b in range(nblocks):
        for what, n in (("gather start", started_g[b]),
                        ("gather wait", waited_g[b]),
                        ("compute", computed[b]),
                        ("scatter start", started_s[b]),
                        ("scatter wait", waited_s[b])):
            if n != 1:
                bad("matched-dma", f"block {b}: {what} ran {n}× (want 1)")
    for kind, inflight in (("gather", gather_inflight),
                           ("scatter", scatter_inflight)):
        for s, b in inflight.items():
            bad("matched-dma",
                f"{kind} of block {b} still in flight on slot {s} at "
                f"step end (unwaited DMA)")
    # sanity: the start→wait pairing above must agree with the kernels'
    # declared DMA semantics metadata
    assert set(DMA_WAIT_FOR_START) == {"gather", "scatter"}
    return out


# ---------------------------------------------------------------------------
# Pass 1a: exhaust the schedule space (every hazard vector in the bound).
# ---------------------------------------------------------------------------
def check_schedule_space(ring_depths=RING_DEPTHS, max_nblocks: int = 6,
                         schedule_fn=kernel_schedule) -> ModelCheckReport:
    """Exhaustively check every ``(ring_depth, nblocks, hazard vector)``
    in the bound. ``schedule_fn`` is injectable so the mutation tests
    can hand the checker a deliberately defective schedule generator.

    Also re-verifies the guard *partition* property structurally: for a
    fixed vector, resolving the guards must keep exactly one
    wait_scatter site per block — that is what :func:`check_events`'
    exactly-once counts certify.
    """
    rep = ModelCheckReport()
    for S in ring_depths:
        for nblocks in range(1, max_nblocks + 1):
            ev_guarded = schedule_fn(nblocks, S)
            for bits in itertools.product((0, 1), repeat=nblocks):
                # plan_blocks never flags block 0 (nothing precedes it);
                # sweeping it anyway is free and proves the schedule
                # never reads hazard[0]
                resolved = [(op, b, s) for op, b, s, g in ev_guarded
                            if g is None or all(bool(bits[f]) is w
                                                for f, w in g)]
                rep.violations.extend(check_events(
                    resolved, nblocks, S, hazard=bits,
                    may_overlap=hazard_may_overlap(bits, S)))
                rep.schedules_checked += 1
    return rep


# ---------------------------------------------------------------------------
# Pass 1b: the planner's flags against an independent oracle, then the
# planner→schedule composition end-to-end on concrete id streams.
# ---------------------------------------------------------------------------
_W_BASE, _C_BASE, _N_BASE = 1000, 4000, 7000
_PLAN_V = 10_000


def _stream_ids(nblocks: int, blk: int, choices, S: int):
    """Concrete (centers, contexts, negatives) realizing an overlap
    pattern: ``choices[(b, m)]`` ∈ {0: none, 1: W overlap, 2: C
    overlap} makes block b share a row with block b-m. Overlap targets
    are always the *last* pair slot of the target block (never itself
    rewritten — overlap writes occupy slots < S-1 ≤ blk-2), so the
    intended intersection is guaranteed to exist."""
    cen = np.zeros((nblocks, blk), np.int32)
    ctx = np.zeros((nblocks, blk), np.int32)
    neg = np.zeros((nblocks, blk, 1), np.int32)
    for b in range(nblocks):
        for j in range(blk):
            cen[b, j] = _W_BASE + b * 100 + j
            ctx[b, j] = _C_BASE + b * 100 + j
            neg[b, j, 0] = _N_BASE + b * 100 + j
    for (b, m), choice in choices.items():
        j = m - 1                   # one dedicated pair slot per offset
        if choice == 1:
            cen[b, j] = _W_BASE + (b - m) * 100 + (blk - 1)
        elif choice == 2:
            # alternate the C-table route: context ids vs negative ids
            # (both land in the shared C row set)
            tgt = _C_BASE + (b - m) * 100 + (blk - 1)
            if (b + m) % 2:
                neg[b, j, 0] = tgt
            else:
                ctx[b, j] = tgt
    return cen.reshape(-1), ctx.reshape(-1), neg.reshape(-1, 1)


def _expected_sets(c, x, n, nblocks: int, blk: int, hot_rows: int):
    """Independent numpy reimplementation of the planner's padded,
    tier-filtered per-block row sets (the oracle the jnp planner is
    checked against). Padding replicates element 0, exactly like
    ``_pad_to_blocks``."""
    def blocks(a):
        a = np.asarray(a).reshape(a.shape[0], -1)
        pad = nblocks * blk - a.shape[0]
        if pad:
            a = np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
        return a.reshape(nblocks, blk, -1)

    cb, xb, nb = blocks(c), blocks(x), blocks(n)
    w_sets, c_sets = [], []
    for b in range(nblocks):
        w = set(int(v) for v in cb[b].ravel() if v >= hot_rows)
        cc = set(int(v) for v in np.concatenate(
            [xb[b].ravel(), nb[b].ravel()]) if v >= hot_rows)
        w_sets.append(w)
        c_sets.append(cc)
    return w_sets, c_sets


def _expected_hazards(w_sets, c_sets, S: int):
    n = len(w_sets)
    hz = np.zeros(n, np.int32)
    for b in range(n):
        for m in range(1, min(S, b + 1)):
            if w_sets[b] & w_sets[b - m] or c_sets[b] & c_sets[b - m]:
                hz[b] = 1
    return hz


def _check_one_plan(c, x, n, blk: int, S: int, hot_rows: int,
                    plan_fn, rep: ModelCheckReport) -> None:
    import jax.numpy as jnp

    B = c.shape[0]
    nblocks = -(-B // blk)
    plan = plan_fn(jnp.asarray(c), jnp.asarray(x), jnp.asarray(n),
                   _PLAN_V, blk, hot_rows=hot_rows, ring_depth=S)
    hz = tuple(int(v) for v in np.asarray(plan.hazard))
    w_sets, c_sets = _expected_sets(c, x, n, nblocks, blk, hot_rows)

    def bad(rule, detail):
        rep.violations.append(Violation(rule, detail, S, nblocks, hz))

    exp = _expected_hazards(w_sets, c_sets, S)
    if not np.array_equal(np.asarray(plan.hazard), exp):
        bad("war-hazard",
            f"planner hazards {list(np.asarray(plan.hazard))} != windowed "
            f"look-behind oracle {list(exp)} (hot_rows={hot_rows}, B={B})")
    # dedup + position-map invariants per block/table
    uw, uc = np.asarray(plan.uw), np.asarray(plan.uc)
    n_w, n_c = np.asarray(plan.n_w), np.asarray(plan.n_c)
    pos = [(uw, n_w, np.asarray(plan.w_pos), np.asarray(plan.cen), w_sets),
           (uc, n_c, np.asarray(plan.cp_pos), np.asarray(plan.ctx), c_sets),
           (uc, n_c, np.asarray(plan.cn_pos), np.asarray(plan.neg), c_sets)]
    for b in range(nblocks):
        for u, cnt, p, ids, sets in pos:
            k = int(cnt[b])
            valid = u[b, :k]
            if not (np.all(np.diff(valid) > 0) if k > 1 else True):
                bad("order", f"block {b}: unique rows not strictly sorted")
            if np.any(u[b, k:] != _PLAN_V):
                bad("order", f"block {b}: padding slots not sentinel")
            if set(int(v) for v in valid) - sets[b]:
                bad("order", f"block {b}: unique set exceeds touched rows")
            for j, rid in enumerate(ids[b]):
                if rid >= hot_rows:
                    if u[b, p[b, j]] != rid:
                        bad("order",
                            f"block {b} pair {j}: position map does not "
                            f"recover row {int(rid)}")
                elif p[b, j] < k:
                    bad("order",
                        f"block {b} pair {j}: hot row {int(rid)} mapped "
                        f"into the DMA'd region (slot {int(p[b, j])} < "
                        f"{k})")
        # every deduped (cold) row must come from the block's touched set
    if int(np.asarray(plan.mask).sum()) != B:
        bad("order", f"mask covers {int(np.asarray(plan.mask).sum())} "
                     f"pairs, batch has {B}")

    # end-to-end: the schedule this plan resolves to must preserve chain
    # fidelity for the ACTUAL row sets
    def set_overlap(b0, b):
        return bool(w_sets[b] & w_sets[b0] or c_sets[b] & c_sets[b0])

    rep.violations.extend(check_events(
        resolve_schedule(hz, S), nblocks, S, hazard=hz,
        may_overlap=set_overlap))
    rep.plans_checked += 1


def check_planner(ring_depths=RING_DEPTHS, max_nblocks: int = 4,
                  include_tails: bool = True,
                  plan_fn=plan_blocks) -> ModelCheckReport:
    """Constructively exhaustive planner check over bounded overlap
    patterns: for every ring depth × block count, every assignment of
    {none, W-overlap, C-overlap} to every (block, window-offset) pair,
    with padded-tail variants and a hot-tier routing case per shape.
    ``plan_fn`` is injectable for the mutation tests."""
    rep = ModelCheckReport()
    K = 1
    for S in ring_depths:
        blk = max(S, 3)     # overlap slots 0..S-2 + one stable last slot
        for nblocks in range(1, max_nblocks + 1):
            slots = [(b, m) for b in range(1, nblocks)
                     for m in range(1, min(S, b + 1))]
            tails = (0, 1) if include_tails and nblocks >= 2 else (0,)
            for pattern in itertools.product((0, 1, 2), repeat=len(slots)):
                choices = dict(zip(slots, pattern))
                cen, ctx, neg = _stream_ids(nblocks, blk, choices, S)
                for tail in tails:
                    B = nblocks * blk - tail
                    _check_one_plan(cen[:B], ctx[:B], neg[:B], blk, S,
                                    0, plan_fn, rep)
            # hot-tier routing: one shared hot id in every block's C set
            # — must produce zero hazards with the tier on, and a full
            # hazard chain with it off
            cen, ctx, neg = _stream_ids(nblocks, blk, {}, S)
            hot_id = 5
            ctx = ctx.copy()
            ctx[::blk] = hot_id                     # pair 0 of every block
            _check_one_plan(cen, ctx, neg, blk, S, hot_rows=10,
                            plan_fn=plan_fn, rep=rep)
            if nblocks >= 2:
                import jax.numpy as jnp
                plan = plan_fn(jnp.asarray(cen), jnp.asarray(ctx),
                               jnp.asarray(neg), _PLAN_V, blk,
                               hot_rows=0, ring_depth=S)
                if not np.asarray(plan.hazard)[1:].all():
                    rep.violations.append(Violation(
                        "war-hazard",
                        "shared cold id across all blocks must flag every "
                        "window", S, nblocks, tuple()))
                if not bool(np.asarray(plan.uc == hot_id).any()):
                    rep.violations.append(Violation(
                        "order", "cold shared id missing from dedup sets",
                        S, nblocks, tuple()))
                rep.plans_checked += 1
    return rep


def run(max_nblocks_schedule: int = 6, max_nblocks_planner: int = 4,
        ring_depths=RING_DEPTHS) -> ModelCheckReport:
    """The full pass: schedule-space sweep + planner integration."""
    rep = check_schedule_space(ring_depths, max_nblocks_schedule)
    return rep.merge(check_planner(ring_depths, max_nblocks_planner))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--max-nblocks", type=int, default=6,
                    help="schedule-space block-count bound (default 6)")
    ap.add_argument("--max-planner-nblocks", type=int, default=4,
                    help="planner overlap-pattern block bound (default 4)")
    ap.add_argument("--ring-depths", default="2,3,4")
    args = ap.parse_args(argv)
    depths = tuple(int(s) for s in args.ring_depths.split(","))
    rep = run(args.max_nblocks, args.max_planner_nblocks, depths)
    print(f"dma_model: {rep.summary()}")
    return 0 if rep.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
