"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing here runs on a chip: each test lowers and compiles with the TPU
compiler for a ``v5e:2x2`` topology described without one attached, so
what Mosaic or XLA would refuse (an unaligned slice, a vector gather, a
program past the chip's 15.75 GB) fails here at no chip time. The HBM
kernels compile at the paper's 300k×500 shape and at ``ZIPF50K``, under
the kernel's own name; the 4-worker vmapped epoch of the tiered engine
must fit one chip, with its planner's scope in the compiled program, a
planner that compiles to no loop and no gather but the noise draw's,
and, as it takes and returns the tables in the kernel's row layout, no
pass over a table outside its loop and almost no scratch memory; so must
the DeepWalk cell's 5 workers at 1,138,499 × 128. The trainer's
stand-alone layout conversions carry the layout scope.

The topology is described inside a module fixture, never at import:
only one process at a time may hold the TPU library, and every test
worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.workloads import ZIPF50K
from repro.core.async_trainer import AsyncShardTrainer
from repro.core.engine import get_engine
from repro.core.sgns import SGNSConfig
from repro.kernels.sgns_fused_pipe import fused_pipe_step
from repro.obs import LAYOUT_SCOPE, PLAN_SCOPE, SGNS_KERNEL

V5E_HBM_BYTES = 15.75e9     # what one v5e chip gives a program
PAPER = {"V": 300_000, "D": 500, "B": 512, "K": 5, "BLK": 256, "HOT": 256}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_step(one_chip, w, hot_rows):
    V, d, B = w["V"], w["D"], w["B"]
    sds = lambda s, t: _sds(one_chip, s, t)

    def step(params, c, x, table, key, lr):
        return fused_pipe_step(params, c, x, table, key, lr,
                               negatives=w["K"], block_pairs=w["BLK"],
                               ring_depth=2, hot_rows=hot_rows,
                               interpret=False)

    compiled = jax.jit(step).lower(
        {"W": sds((V, d), jnp.float32), "C": sds((V, d), jnp.float32)},
        sds((B,), jnp.int32), sds((B,), jnp.int32),
        {"prob": sds((V,), jnp.float32), "alias": sds((V,), jnp.int32)},
        sds((2,), jnp.uint32), sds((), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{SGNS_KERNEL}." in text       # the kernel under its own name
    return compiled


@pytest.mark.parametrize("workload", ["paper", "zipf50k"])
@pytest.mark.parametrize("engine", ["pallas_fused_pipe", "pallas_fused_tiered"])
def test_hbm_kernel_step_compiles_for_v5e(one_chip, workload, engine):
    w = PAPER if workload == "paper" else ZIPF50K
    hot = w["HOT"] if engine == "pallas_fused_tiered" else 0
    compiled = _compile_step(one_chip, w, hot)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * w["V"] * w["D"] * 4


def _assert_planner_has_no_loop_or_gather(text, V):
    """Under the planner's scope the compiled program has no ``while``
    (a binary search compiles to one) and gathers only from the
    vocabulary's ``(…, V)`` noise tables: the alias draw's lookups."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text))
    for line in text.splitlines():
        if PLAN_SCOPE not in line:
            continue
        assert not re.search(r" while\(", line), line
        gather = re.search(r" gather\((%[\w.\-]+),", line)
        if gather:
            assert shapes[gather[1]].split(",")[-1] == str(V), line


def _tiered_trainer(n, V, d):
    engine = get_engine("pallas_fused_tiered", interpret=False,
                        hot_rows=PAPER["HOT"], block_pairs=PAPER["BLK"])
    return AsyncShardTrainer(
        cfg=SGNSConfig(vocab_size=V, dim=d, window=10, negatives=PAPER["K"]),
        num_workers=n, total_steps=1000, engine=engine)


def _compile_tiered_epoch(one_chip, n, V, d, S=128, B=512):
    """The trainer's epoch compiled as it runs: the tables in the
    engine's packed layout, taken and returned."""
    tr = _tiered_trainer(n, V, d)
    sds = lambda s, t: _sds(one_chip, s, t)
    table = jax.eval_shape(tr.engine.pack,
                           jax.ShapeDtypeStruct((n, V, d), jnp.float32))
    return tr._jit_epoch().lower(
        {"W": sds(table.shape, table.dtype), "C": sds(table.shape, table.dtype)},
        sds((n, S, B), jnp.int32), sds((n, S, B), jnp.int32),
        {"prob": sds((n, V), jnp.float32), "alias": sds((n, V), jnp.int32)},
        sds((n, 2), jnp.uint32), sds((n,), jnp.int32)).compile()


def _assert_fits_with_tables_donated(compiled, n, V, d):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 2 * n * V * d * 4     # tables donated
    assert total <= V5E_HBM_BYTES, total


def _assert_entry_moves_no_table(compiled, n, V):
    """Outside its loop the epoch makes no pass over a table (no copy,
    pad, reshape, broadcast or slice of an ``(n, V, …)`` array: only the
    parameters, the ``while`` and its results), and its scratch memory
    is no table's: under 64 MiB."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ops = re.findall(rf"(%[\w.\-]+) = \w+\[{n},{V}(?:,\d+)*\]\S* ([\w\-]+)\(",
                     entry)
    assert len(ops) >= 4, ops         # two tables in, two out of the loop
    for name, op in ops:
        assert op in ("parameter", "get-tuple-element"), (name, op)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2 ** 20, temp


def test_four_worker_tiered_epoch_fits_one_v5e(one_chip):
    """The paper's shape, 4 workers vmapped on one chip: the compiled
    epoch (tables donated, in the kernel's row layout) must fit the
    chip's memory and convert no table; the trainer's stand-alone
    conversions carry the layout scope."""
    n, V, d = 4, PAPER["V"], PAPER["D"]
    compiled = _compile_tiered_epoch(one_chip, n, V, d)
    text = compiled.as_text()
    for name in (SGNS_KERNEL, PLAN_SCOPE):
        assert name in text, name
    assert LAYOUT_SCOPE not in text
    _assert_planner_has_no_loop_or_gather(text, V)
    _assert_fits_with_tables_donated(compiled, n, V, d)
    _assert_entry_moves_no_table(compiled, n, V)
    layout = _tiered_trainer(n, V, d).layout
    plain = _sds(one_chip, (n, V, d), jnp.float32)
    packed = _sds(one_chip, (n, V, 1, 512), jnp.float32)
    for convert, table in ((layout.pack, plain), (layout.unpack, packed)):
        assert LAYOUT_SCOPE in convert.lower(table).compile().as_text()


def test_five_worker_deepwalk_epoch_fits_one_v5e(one_chip):
    """The DeepWalk cell's share of its deployment: 5 workers of the
    1,138,499-node YouTube graph at d = 128 (no lane padding) on one
    chip, the hot tier's counter summed in the epoch."""
    n, V, d = 5, 1_138_499, 128
    compiled = _compile_tiered_epoch(one_chip, n, V, d)
    assert SGNS_KERNEL in compiled.as_text()
    _assert_fits_with_tables_donated(compiled, n, V, d)
    _assert_entry_moves_no_table(compiled, n, V)
