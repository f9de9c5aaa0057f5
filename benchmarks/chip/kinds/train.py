"""Training traffic: the paper's asynchronous SGNS sub-model training,
driven for a window through the system's own pieces.

Set-up builds the corpus from the seed, ``prepare_training`` (worker
vocabularies, noise tables, pair streams), one ``AsyncShardTrainer``
and its tables, and the prefetched chunk stream; it then drives that
same trainer through its first ``check_chunks`` chunks (the first
compiles it), reading what the checks compare. The window streams
further chunks through ``trainer.epoch`` until ``--seconds`` have
passed, with at most two chunks in flight (waited for, not read back),
and ends on a chunk boundary after ``block_until_ready``.

After the window the program's state is freed and the plain references
(``reference/ingest.py``, ``reference/sgns.py``) recompute the checked
chunks from the seed: the ids each worker was fed, every step's loss,
and the norm of each table's change after the first chunk and after
``check_chunks`` chunks.

Every table holds the configuration's ``max_vocab`` rows, whatever
union vocabulary the seed's corpus draws: the rows past the union are
never fed and have no weight in any noise table. So every seed runs
the same shapes, and only a cell's first run compiles.
"""

from __future__ import annotations

import collections
import dataclasses
import sys
import time

import numpy as np

from benchmarks.chip import counting
from benchmarks.chip.device import memory_peak_bytes
from benchmarks.chip.harness import Check, Outcome, Run
from benchmarks.chip.reference import ingest as ref_ingest
from benchmarks.chip.reference import sgns as ref_sgns
from benchmarks.chip.traffic import corpus as corpus_gen

GENERATORS = {"semantic_corpus": corpus_gen.semantic_corpus}
TABLES = ("W", "C")
IN_FLIGHT = 2          # chunks dispatched ahead of the last finished one
_KEY_TAG = 0xC4       # folded into the seed's key for the chunk keys


def make_corpus(traffic: dict, seed: int):
    args = dict(traffic["corpus"])
    return GENERATORS[args.pop("generator")](seed, **args)


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers the checks may compare (the traffic's ``limits`` name
    those compared): the largest relative gap of a step loss, and, for
    each of the tables W and C by its worst leaf (one worker's
    table), the gap between the program's and the reference's norm of
    the change after the first chunk and after the last checked one,
    measured against the reference's norm of that leaf or of the
    table's median leaf, whichever is larger. Leaves whose reference
    change is under a thousandth of the table's median leaf's are left
    out. Each table has a scale of its own: C starts at zero, and after
    the first chunk W has moved a third to a fifth as far as C."""
    out = {"pair_id_mismatches": float(
        np.sum(prog["centers"] != ref["centers"])
        + np.sum(prog["contexts"] != ref["contexts"]))}
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out["loss_gap"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    for name, key in (("grad_norm_gap", "first"), ("change_norm_gap", "last")):
        p = np.asarray(prog[key], np.float64)
        r = np.asarray(ref[key], np.float64)
        for t, table in enumerate(TABLES):
            med = float(np.median(r[:, t]))
            keep = r[:, t] >= 1e-3 * med
            scale = np.maximum(r[:, t], med)
            out[f"{name}.{table}"] = float(
                np.max((np.abs(p[:, t] - r[:, t]) / scale)[keep]))
    return out


def padded(counts: np.ndarray, rows: int) -> np.ndarray:
    """``counts`` over the union's rows, with the table's further rows
    at zero."""
    if len(counts) > rows:
        raise ValueError(f"the union vocabulary ({len(counts)} words) "
                         f"exceeds the tables' {rows} rows")
    return np.pad(counts, (0, rows - len(counts)))


def reference_ids(run: Run, tokens, offsets) -> dict:
    """The ids the checked chunks must hold (``reference/ingest.py``),
    ``(chunks, workers, S, B)``, and each worker's noise over the
    tables' rows."""
    cfg, tr = run.config, run.traffic
    rows = cfg["max_vocab"]
    ing = ref_ingest.build(tokens, offsets, tr["corpus"]["vocab_size"],
                           tr["workers"], tr["rate"], cfg["base_min_count"],
                           cfg["max_vocab"], run.seed32, tr["strategy"])
    ids = [ref_ingest.worker_chunks(
        tokens, offsets, ing, w, chunks=tr["check_chunks"],
        steps=tr["steps_per_chunk"], batch=tr["batch"],
        window=cfg["window"], subsample_t=cfg["subsample_t"],
        sentences_per_block=tr["sentences_per_block"], seed=run.seed32)
        for w in range(tr["workers"])]
    return {"centers": np.stack([c for c, _ in ids], axis=1),
            "contexts": np.stack([x for _, x in ids], axis=1),
            "noise": [padded(ing.noise(w), rows)
                      for w in range(tr["workers"])],
            "rows": rows}


def reference_readings(run: Run, ids: dict, *, dtype="float32",
                       keep: float = 1.0) -> dict:
    """The plain SGNS reference over the checked chunks, one worker at a
    time (``dtype`` and ``keep`` make the control and a fault from the
    same code)."""
    import jax
    import jax.numpy as jnp

    cfg, tr = run.config, run.traffic
    n, S = tr["workers"], tr["steps_per_chunk"]
    V, d, dt = ids["rows"], cfg["dim"], jnp.dtype(dtype)
    key = jax.random.PRNGKey(run.seed32)
    losses = np.zeros(ids["centers"].shape[:3], np.float32)   # (chunks, n, S)
    first = np.zeros((n, 2))
    last = np.zeros((n, 2))
    for w in range(n):
        prob, alias = (jnp.asarray(a) for a in
                       ref_sgns.alias_table(ids["noise"][w]))
        W, C = ref_sgns.init_table(key, n, w, V, d, dt)
        for k in range(tr["check_chunks"]):
            W, C, loss = ref_sgns.train_chunk(
                W, C, jnp.asarray(ids["centers"][k, w]),
                jnp.asarray(ids["contexts"][k, w]), prob, alias,
                ref_sgns.step_seeds(chunk_key(run, k), n, w, S),
                jnp.int32(k * S), k=cfg["negatives"],
                block=cfg["update_block_pairs"],
                total_steps=tr["lr_total_steps"], lr0=cfg["lr"],
                lr_min=cfg["lr_min"], keep=keep)
            losses[k, w] = np.asarray(loss)
            if k in (0, tr["check_chunks"] - 1):
                W0, C0 = ref_sgns.init_table(key, n, w, V, d, dt)
                norms = np.asarray(ref_sgns.change_norms(W, C, W0, C0))
                del W0, C0
                if k == 0:
                    first[w] = norms
                last[w] = norms
        del W, C
    return {"centers": ids["centers"], "contexts": ids["contexts"],
            "losses": losses, "first": first, "last": last}


def host_device():
    """The host's CPU device, or None (JAX's default device) where JAX
    was started without its CPU backend."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def chunk_key(run: Run, k: int):
    import jax

    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(run.seed32), _KEY_TAG), k)


class Program:
    """The system under test, set up for one cell: the trainer, its
    tables, the noise tables and the chunk stream."""

    def __init__(self, run: Run, tokens, offsets):
        import jax
        from repro.core.async_trainer import AsyncShardTrainer
        from repro.core.driver import prepare_training
        from repro.core.sgns import SGNSConfig
        from repro.data.corpus import Corpus
        from repro.data.pairs import stack_noise_tables
        from repro.data.pipeline import prefetch_chunks

        cfg, tr = run.config, run.traffic
        self.run = run
        self.S, self.B = tr["steps_per_chunk"], tr["batch"]
        sgns_cfg = SGNSConfig(vocab_size=0, dim=cfg["dim"],
                              window=cfg["window"], negatives=cfg["negatives"],
                              lr=cfg["lr"], lr_min=cfg["lr_min"],
                              seed=run.seed32)
        # The epoch is counted only as far as one chunk: the learning
        # rate follows the deployment's epoch (lr_total_steps), not the
        # window's. The noise tables prepare_training stacks are sized
        # to the seed's union vocabulary and are replaced below, so they
        # are stacked on the host's CPU device: on the chip each new
        # size would compile its stacking ops again in every set-up.
        with jax.default_device(host_device()):
            self.setup = prepare_training(
                Corpus(tokens=tokens, offsets=offsets),
                tr["corpus"]["vocab_size"], tr["strategy"], tr["workers"],
                sgns_cfg, epochs=1, batch_size=self.B, rate=tr["rate"],
                window=cfg["window"], subsample_t=cfg["subsample_t"],
                max_vocab=cfg["max_vocab"],
                base_min_count=cfg["base_min_count"], seed=run.seed32,
                max_steps_per_epoch=self.S, engine=cfg["engine"],
                steps_per_chunk=self.S,
                sentences_per_block=tr["sentences_per_block"],
                process_index=0, process_count=1)
        plan = self.setup.plan
        self.workers = plan.num_local
        rows = cfg["max_vocab"]
        self.trainer = AsyncShardTrainer(
            cfg=dataclasses.replace(self.setup.cfg, vocab_size=rows),
            num_workers=self.workers, total_steps=tr["lr_total_steps"],
            backend="vmap", engine=self.setup.engine)
        self.neg_table = stack_noise_tables(
            [padded(s.vocab.counts, rows) for s in self.setup.streams],
            kind=self.setup.engine.table_kind)
        self.key = jax.random.PRNGKey(run.seed32)
        self.params = self.trainer.init(self.key)
        stream = plan.chunk_stream(
            self.setup.streams, batch_size=self.B, steps_per_chunk=self.S,
            sentences_per_block=tr["sentences_per_block"])
        self.chunks = prefetch_chunks(stream.chunks(0, None),
                                      depth=tr["prefetch"])
        self.k = 0

    def next_chunk(self):
        return next(self.chunks)

    def step(self, c, x):
        """One call of the window: a chunk through ``trainer.epoch``."""
        self.params, losses = self.trainer.epoch(
            self.params, c, x, self.neg_table, chunk_key(self.run, self.k),
            step0=self.k * self.S)
        self.k += 1
        return losses

    def change_norms(self):
        p0 = self.trainer.init(self.key)
        out = ref_sgns.change_norms(self.params["W"], self.params["C"],
                                    p0["W"], p0["C"])
        return np.asarray(out)

    def close(self):
        self.chunks.close()
        self.params = None


def program_readings(prog: Program, chunks: int) -> dict:
    """The first ``chunks`` chunks through the trainer's own call and
    feed: the ids it was fed, its step losses, and its tables' change
    after the first chunk and after the last (read before the next
    call takes them)."""
    fed_c, fed_x, losses, first = [], [], [], None
    for k in range(chunks):
        c, x = prog.next_chunk()
        fed_c.append(np.asarray(c))
        fed_x.append(np.asarray(x))
        losses.append(np.asarray(prog.step(c, x)))
        if k == 0:
            first = prog.change_norms()
    return {"centers": np.stack(fed_c), "contexts": np.stack(fed_x),
            "losses": np.stack(losses), "first": first,
            "last": prog.change_norms()}


def run(run: Run) -> Outcome:
    import jax

    tr = run.traffic
    marks = [("start", run.t_start)]
    tokens, offsets = make_corpus(tr, run.seed)
    marks.append(("corpus", time.perf_counter()))
    prog = Program(run, tokens, offsets)
    marks.append(("program", time.perf_counter()))
    readings = program_readings(prog, tr["check_chunks"])
    marks.append(("checked_chunks", time.perf_counter()))
    print("setup s: " + ", ".join(f"{name} {t - t0:.2f}" for (_, t0), (
        name, t) in zip(marks, marks[1:])), file=sys.stderr)

    pairs_per_chunk = prog.workers * prog.S * prog.B
    inflight = collections.deque()
    window_ids, window_losses = [], []
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    with run.window():
        while True:
            with run.span("ingest.wait"):
                c, x = prog.next_chunk()
            with run.span("dispatch"):
                inflight.append(prog.step(c, x))
            if run.trace:
                window_ids.append((c, x))
            if len(inflight) > IN_FLIGHT:
                with run.span("sync"):
                    window_losses.append(
                        inflight.popleft().block_until_ready())
            if time.perf_counter() - t0 >= run.seconds:
                break
        with run.span("drain"):
            jax.block_until_ready(prog.params)
            window_losses += list(inflight)
        t1 = time.perf_counter()       # before a trace is written out
    # read back once the window has closed, as the program's own loop
    # (``train_submodels``) reads its losses after the epoch
    window_losses = [np.asarray(a) for a in window_losses]
    chunks = len(window_losses)
    peak = memory_peak_bytes(run.devices)
    trace = run.read_trace()
    traced = [(np.asarray(c), np.asarray(x)) for c, x in window_ids]
    prog.close()
    del prog

    t_ref = time.perf_counter()
    ids = reference_ids(run, tokens, offsets)
    values = gaps(readings, reference_readings(run, ids))
    print(f"reference {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    limits = tr["limits"]
    checks = [Check(name, values[name], limits[name]) for name in limits]
    failed = sum(not np.all(np.isfinite(a)) for a in window_losses)
    counters = {"chunks": chunks, "pairs": chunks * pairs_per_chunk,
                "window_s": t1 - t0, "chips": len(run.devices),
                "flops_per_pair": counting.sgns_flops_per_pair(
                    run.config["dim"], run.config["negatives"])}
    if traced:
        counters["bytes"] = sum(
            counting.sgns_chunk_bytes(c[w], x[w], ids["noise"][w],
                                      run.config["negatives"],
                                      run.config["dim"])
            for c, x in traced for w in range(c.shape[0]))
        counters["traced_pairs"] = len(traced) * pairs_per_chunk
    return Outcome(
        end_to_end={"train_pairs_per_s":
                    chunks * pairs_per_chunk / (t1 - t0) / len(run.devices)},
        setup_s=setup_s, attempted=chunks, failed=int(failed), checks=checks,
        memory_peak_bytes=peak, counters=counters, trace=trace)
