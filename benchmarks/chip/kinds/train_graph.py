"""Graph training traffic: DeepWalk's random-walk pairs through the
paper's asynchronous SGNS sub-models, driven for a window through the
system's own pieces.

Set-up draws the graph from the seed (``traffic/graph.py``), runs the
program's graph set-up ``prepare_graph_training`` (degree-ordered rows,
noise from degrees, one walk stream per worker) for this chip's process
of the deployment's ``HostShardPlan``, builds one ``AsyncShardTrainer``
over the workers that process holds and the prefetched chunk stream of
``plan.chunk_stream``, and trains the first ``check_chunks`` chunks as
the text cell does (``kinds/train.py``). The window is the text cell's:
chunks through ``trainer.epoch`` until ``--seconds`` have passed, at
most two in flight, ending on a chunk boundary after
``block_until_ready``.

After the window the plain references recompute the checked chunks from
the seed: ``reference/walks.py`` the ids each held worker was fed,
``reference/sgns.py`` the training, compared by the text cell's
``gaps``.
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
from benchmarks.chip.kinds import train
from benchmarks.chip.reference import walks as ref_walks
from benchmarks.chip.traffic import graph as graph_gen

# the program's graph set-up: a program without it fails here, at once
from repro.core.driver import prepare_graph_training
from repro.data.graph import Graph

GENERATORS = {"chung_lu": graph_gen.chung_lu_edges}


def make_graph(run: Run) -> tuple[np.ndarray, np.ndarray]:
    cfg, args = run.config, dict(run.traffic["graph"])
    return GENERATORS[args.pop("generator")](
        run.seed, cfg["nodes"], cfg["edges"], cfg["max_degree"],
        cfg["degree_exponent"], **args)


class Program(train.Program):
    """The system under test for one graph cell: the held workers'
    trainer, tables, noise tables and chunk stream."""

    def __init__(self, run: Run, src, dst):
        import jax
        from repro.core.async_trainer import AsyncShardTrainer
        from repro.core.sgns import SGNSConfig
        from repro.data.pipeline import prefetch_chunks

        cfg, tr = run.config, run.traffic
        self.run = run
        self.S, self.B = tr["steps_per_chunk"], tr["batch"]
        sgns_cfg = SGNSConfig(vocab_size=0, dim=cfg["dim"],
                              window=cfg["window"], negatives=cfg["negatives"],
                              lr=cfg["lr"], lr_min=cfg["lr_min"],
                              seed=run.seed32)
        # on the host's CPU device, as the text cell's set-up: nothing of
        # it compiles on the chip
        with jax.default_device(train.host_device()):
            self.setup = prepare_graph_training(
                Graph.from_edges(src, dst, cfg["nodes"]), tr["workers"],
                sgns_cfg, epochs=1, batch_size=self.B, rate=tr["rate"],
                window=cfg["window"], walks_per_node=cfg["walks_per_node"],
                walk_length=cfg["walk_length"], seed=run.seed32,
                engine=cfg["engine"],
                steps_per_chunk=self.S, walks_per_block=tr["walks_per_block"],
                process_index=tr["process_index"],
                process_count=tr["process_count"])
        plan = self.setup.plan
        self.workers = plan.num_local
        if self.workers != cfg["workers_held"]:
            raise ValueError(f"process {plan.describe()} holds "
                             f"{self.workers} workers, the configuration "
                             f"{cfg['workers_held']}")
        self.trainer = AsyncShardTrainer(
            cfg=self.setup.cfg, num_workers=self.workers,
            total_steps=tr["lr_total_steps"], backend="vmap",
            engine=self.setup.engine)
        self.neg_table = jax.device_put(
            jax.tree.map(lambda a: a[plan.start:plan.stop],
                         self.setup.neg_table), run.devices[0])
        self.key = jax.random.PRNGKey(run.seed32)
        self.params = self.trainer.init(self.key)
        stream = plan.chunk_stream(
            self.setup.streams, batch_size=self.B, steps_per_chunk=self.S,
            sentences_per_block=tr["walks_per_block"])
        self.chunks = prefetch_chunks(stream.chunks(0, None),
                                      depth=tr["prefetch"])
        self.k = 0


def reference_ids(run: Run, src, dst) -> dict:
    """The ids the held workers' checked chunks must hold
    (``reference/walks.py``), ``(chunks, held workers, S, B)``, and each
    one's noise over the rows."""
    cfg, tr = run.config, run.traffic
    g = ref_walks.build(src, dst, cfg["nodes"])
    held = ref_walks.local_workers(tr["workers"], tr["process_index"],
                                   tr["process_count"])
    ids = [ref_walks.worker_chunks(
        g, w, rate=tr["rate"], walks_per_node=cfg["walks_per_node"],
        walk_length=cfg["walk_length"], window=cfg["window"],
        walks_per_block=tr["walks_per_block"], chunks=tr["check_chunks"],
        steps=tr["steps_per_chunk"], batch=tr["batch"], seed=run.seed32)
        for w in held]
    noise = g.noise()
    return {"centers": np.stack([c for c, _ in ids], axis=1),
            "contexts": np.stack([x for _, x in ids], axis=1),
            "noise": [noise] * len(held), "rows": cfg["nodes"],
            "held": len(held)}


def reference_readings(run: Run, ids: dict, **kw) -> dict:
    """The text cell's plain SGNS reference over the held workers: the
    trainer splits its keys over them."""
    held = dataclasses.replace(run, traffic={**run.traffic,
                                             "workers": ids["held"]})
    return train.reference_readings(held, ids, **kw)


def run(run: Run) -> Outcome:
    import jax

    tr = run.traffic
    marks = [("start", run.t_start)]
    src, dst = make_graph(run)
    marks.append(("graph", time.perf_counter()))
    prog = Program(run, src, dst)
    marks.append(("program", time.perf_counter()))
    readings = train.program_readings(prog, tr["check_chunks"])
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
            if len(inflight) > train.IN_FLIGHT:
                with run.span("sync"):
                    window_losses.append(
                        inflight.popleft().block_until_ready())
            if time.perf_counter() - t0 >= run.seconds:
                break
        with run.span("drain"):
            jax.block_until_ready(prog.params)
            window_losses += list(inflight)
        t1 = time.perf_counter()       # before a trace is written out
    window_losses = [np.asarray(a) for a in window_losses]
    chunks = len(window_losses)
    peak = memory_peak_bytes(run.devices)
    trace = run.read_trace()
    traced = [(np.asarray(c), np.asarray(x)) for c, x in window_ids]
    prog.close()
    del prog

    t_ref = time.perf_counter()
    ids = reference_ids(run, src, dst)
    values = train.gaps(readings, reference_readings(run, ids))
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
