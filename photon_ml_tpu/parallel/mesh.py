"""Device mesh + named shardings: the distributed substrate.

Reference parity: the Spark seams — RDDLike.scala, broadcast wrappers
(SURVEY.md §2.5, PARITY.md L25) — dissolved rather than ported. This module
replaces the reference's entire Spark communication layer
(SURVEY.md §2.5): RDD treeAggregate -> XLA psum reduction trees over ICI;
driver broadcast -> replicated sharding; custom partitioners
(LongHashPartitioner, RandomEffectDataSetPartitioner) -> named shardings of
the sample and entity axes. There is no hand-written collective call in the
training path: data enters sharded, jit inserts the collectives.

Mesh convention:
- "data":  sample axis (and entity axis for random-effect buckets) — DP/EP
- "model": feature axis for giant fixed-effect coordinates — sharded
  coefficient vectors with reduce-scattered gradients (SURVEY.md §7,
  1B-coefficient case)

Multi-host: build the mesh over jax.devices() after jax.distributed
initialization; ICI carries within-slice axes, DCN across slices.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.telemetry.tracing import span


def make_mesh(
    data: int | None = None,
    model: int = 1,
    *,
    devices=None,
) -> Mesh:
    """Create a ("data", "model") mesh. Defaults to all devices on "data"."""
    devices = list(devices if devices is not None else jax.devices())
    if data is None:
        data = len(devices) // model
    if data * model != len(devices):
        devices = devices[: data * model]
    grid = np.array(devices).reshape(data, model)
    return Mesh(grid, axis_names=("data", "model"))


def place(x, sharding, *, put=jax.device_put, group: str = "data"):
    """``x`` laid out as ``sharding``; the one way inputs reach a mesh.

    An array that already has the layout comes back untouched, so placing
    resident inputs moves nothing. A HOST array is cut on the host and every
    shard goes straight to its own device (``jax.device_put`` of a numpy
    array does that; ``multihost.global_put`` across processes), under the
    span ``train/shard/assemble`` {group, bytes}: no device ever holds more
    than its share of it. Anything else (a device array in another layout)
    is ``put`` as it always was."""
    if isinstance(x, jax.Array) and x.sharding == sharding:
        return x
    if isinstance(x, np.ndarray):
        with span("train/shard/assemble", group=group, bytes=int(x.nbytes)):
            return put(x, sharding)
    return put(x, sharding)


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated (the 'broadcast' of the reference —
    done once, not per iteration; reference re-broadcast the coefficient
    vector every optimizer step, FixedEffectCoordinate.scala:143)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_batch(batch: LabeledPointBatch, mesh: Mesh, *, feature_sharded: bool = False) -> LabeledPointBatch:
    """Shard a batch along the sample axis ("data"); optionally shard the
    feature axis along "model" for giant coordinates."""
    fspec = P("data", "model" if feature_sharded else None)
    vspec = P("data")
    n = batch.num_samples
    per = mesh.shape["data"]
    if n % per != 0:
        batch = batch.pad_to(((n + per - 1) // per) * per)
    return LabeledPointBatch(
        features=jax.device_put(batch.features, NamedSharding(mesh, fspec)),
        labels=jax.device_put(batch.labels, NamedSharding(mesh, vspec)),
        offsets=jax.device_put(batch.offsets, NamedSharding(mesh, vspec)),
        weights=jax.device_put(batch.weights, NamedSharding(mesh, vspec)),
    )


def shard_game_dataset(dataset, mesh: Mesh):
    """Shard a GameDataset's sample-axis arrays over "data". Entity-bucket
    tensors shard their entity axis over "data" when solved (the vmapped
    solver's batch dimension). Fields that are host arrays go shard by shard
    (:func:`place`): a data set larger than one chip is laid out over four
    without ever being whole on one."""
    vspec = NamedSharding(mesh, P("data"))

    n = dataset.num_samples
    per = mesh.shape["data"]
    if n % per != 0:
        raise ValueError(
            f"sample count {n} not divisible by data-axis size {per}; "
            "pad with zero-weight rows first"
        )
    dataset = dataclasses.replace(
        dataset,
        labels=place(dataset.labels, vspec),
        offsets=place(dataset.offsets, vspec),
        weights=place(dataset.weights, vspec),
        feature_shards={
            k: place(v, NamedSharding(mesh, P("data", None)))
            for k, v in dataset.feature_shards.items()
        },
        entity_idx={
            k: place(v, vspec) for k, v in dataset.entity_idx.items()
        },
    )
    return dataset
