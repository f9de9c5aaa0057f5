"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init,
smoke tests keep seeing 1 device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e pod mesh: 16×16 = 256 chips per pod; 2 pods multi-pod.

    Axes: ``data`` (FSDP/batch), ``model`` (tensor/expert parallel),
    plus ``pod`` (pure DP over DCN) when multi_pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_worker_mesh(num_workers: int, devices=None) -> Mesh:
    """Mesh for the paper's async SGNS training: one ``worker`` axis over
    ``devices`` (default: every device of the runtime), each device
    training ``num_workers / len(devices)`` workers, zero collectives
    inside the step."""
    devices = list(jax.devices() if devices is None else devices)
    if num_workers % len(devices):
        raise ValueError(
            f"{num_workers} workers do not divide over {len(devices)} "
            f"devices; use a multiple of the device count")
    return Mesh(np.asarray(devices), ("worker",))


def make_smoke_mesh() -> Mesh:
    """1-device mesh with production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def multihost_train_kwargs(num_workers: int,
                           processes: int | None = None
                           ) -> tuple[int, dict]:
    """Resolve a CLI ``--processes`` value (None → the jax runtime's)
    and the extra ``train_submodels`` kwargs a run on several devices
    needs: the workers are sharded under ``shard_map`` over a worker mesh
    of every device — on one host with several chips as on several
    hosts, where the per-chunk input assembly is the sole inter-host
    exchange. One device keeps the ``vmap`` backend. Shared by
    ``train_sgns`` and ``train_w2v_100m``."""
    if processes is None:
        processes = jax.process_count()
    kwargs: dict = {}
    if processes > 1 or jax.device_count() > 1:
        kwargs = dict(backend="shard_map", mesh=make_worker_mesh(num_workers))
    return processes, kwargs


def assemble_worker_array(mesh: Mesh, plan, local: np.ndarray,
                          axis_name: str = "worker") -> jax.Array:
    """Global ``(num_workers, ...)`` device array from this host's
    ``(plan.num_local, ...)`` block of worker-leading data.

    ``plan`` is a :class:`repro.data.pipeline.HostShardPlan`. Each host
    hands in only the rows of the workers it extracted; the global array
    is sharded ``P(axis_name)`` over the mesh. Multi-host, this is
    :func:`jax.make_array_from_process_local_data` — no host ever
    materializes another host's chunk. Single-host (including every
    simulated-``process_count`` test, which concatenates the per-plan
    blocks itself before calling this) it is a plain sharded
    ``device_put`` of the full array.
    """
    local = np.asarray(local)
    if local.shape[0] != plan.num_local:
        raise ValueError(
            f"local block has {local.shape[0]} worker rows; "
            f"{plan.describe()} expects {plan.num_local}")
    sharding = NamedSharding(mesh, P(axis_name))
    if plan.process_count == 1:
        return jax.device_put(local, sharding)
    plan.validate_for_mesh(mesh)
    global_shape = (plan.num_workers,) + local.shape[1:]
    return jax.make_array_from_process_local_data(sharding, local,
                                                  global_shape)
