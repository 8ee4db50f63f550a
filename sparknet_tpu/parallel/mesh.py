"""Device-mesh construction and multi-host initialization.

TPU-native analog of the reference's cluster plumbing: where SparkNet got its
worker set from Spark executors (ref: src/main/scala/apps/CifarApp.scala:27-33
`new SparkContext`; workers pinned via WorkerStore.scala:5-25) and Caffe got
its GPU set from `--gpu=0,1` (ref: caffe/tools/caffe.cpp:209-211), here the
"cluster" is a `jax.sharding.Mesh` over the pod slice, and multi-host comes
from `jax.distributed.initialize` over DCN.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported for the trainers
from jax.sharding import Mesh

from sparknet_tpu.common import get_config


def local_device_count() -> int:
    return jax.local_device_count()


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bring-up (replaces the Spark driver/executor topology;
    ref: README.md:26 spark-submit deployment).  No-op on a single host
    with no coordinator configured."""
    if coordinator_address is None and num_processes is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def data_parallel_mesh(num_devices: int | None = None,
                       devices=None) -> Mesh:
    """1-D mesh over all (or the first N) devices on the data axis —
    the direct analog of SparkNet's flat worker set.  ``devices``
    restricts the pool the mesh is cut from (default: all visible)."""
    cfg = get_config()
    devices = list(devices) if devices is not None else jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return Mesh(np.array(devices), axis_names=(cfg.data_axis,))


def sized_data_mesh(width: int, devices=None) -> Mesh:
    """Shape-parameterized mesh re-formation: a fresh 1-D data mesh over
    the first ``width`` devices of ``devices`` (default: all visible).

    This is the elastic-membership primitive (``parallel/elastic.py``):
    where SparkNet re-formed its worker set from whatever executors Spark
    still had (the RDD fault-tolerance layer, ref: CifarApp.scala:27-33 —
    design-replaced here), the TPU rebuild re-forms the MESH — the same
    device pool re-cut at a new width between averaging rounds, so the
    per-width round programs differ only in the mesh they close over.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if not (1 <= width <= len(devices)):
        raise ValueError(
            f"cannot form a {width}-wide data mesh from "
            f"{len(devices)} device(s) (need 1 <= width <= pool size)")
    cfg = get_config()
    return Mesh(np.array(devices[:width]), axis_names=(cfg.data_axis,))


def auto_mesh(
    num_devices: int | None = None,
    model_parallel: int = 1,
    seq_parallel: int = 1,
) -> Mesh:
    """(data, model[, seq]) mesh.  `model_parallel` is the tensor-parallel
    degree, `seq_parallel` the sequence/context-parallel degree (ring /
    Ulysses attention); the rest of the devices go to data parallelism.
    On real TPU hardware the default device order keeps the minor-most
    mesh axis on ICI-adjacent chips; the reshape here places seq
    minor-most (then model), so the per-step ppermute/all_to_all traffic
    of sequence parallelism rides the fastest links."""
    cfg = get_config()
    devices = jax.devices()
    n = num_devices if num_devices is not None else len(devices)
    devices = devices[:n]
    denom = model_parallel * seq_parallel
    if n % denom != 0:
        raise ValueError(
            f"{n} devices not divisible by model_parallel={model_parallel} "
            f"* seq_parallel={seq_parallel}"
        )
    dims = [n // denom, model_parallel]
    axes = [cfg.data_axis, cfg.model_axis]
    if seq_parallel > 1:
        dims.append(seq_parallel)
        axes.append(cfg.seq_axis)
    arr = np.array(devices).reshape(dims)
    return Mesh(arr, axis_names=tuple(axes))


def mesh_seq_size(mesh: Mesh) -> int:
    cfg = get_config()
    return mesh.shape.get(cfg.seq_axis, 1)


def mesh_data_size(mesh: Mesh) -> int:
    cfg = get_config()
    return mesh.shape.get(cfg.data_axis, 1)


def mesh_model_size(mesh: Mesh) -> int:
    cfg = get_config()
    return mesh.shape.get(cfg.model_axis, 1)
