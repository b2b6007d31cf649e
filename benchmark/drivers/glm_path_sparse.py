"""Traffic kind ``glm_path_sparse``: the fits of ``glm_path`` on a SPARSE
fixed effect: a ``SparseLabeledPointBatch`` in the hybrid layout (the hottest
columns a dense [n, k_hot] head, the cold entries an ELL block at the builder's
auto width, what overflows it a flat triple) at a feature dimension no dense
block could hold.

The episode is ``drivers/glm_path.py``'s OWN method, inherited: one call of
``estimators.train_glm`` on the placed batch over the configuration's λ grid
as one warm-started path from zero, every λ's model scoring the resident
validation block (``GeneralizedLinearModel.score``, which takes a sparse block
through ``data/sparse_batch.sparse_product``), ended by a host read of the
coefficient vectors and of those margins. So are ``end_to_end``, ``host_data``,
``release`` and ``verify``. What differs is set-up and what is counted:

- the generator is ``benchmark/datagen_sparse.py`` (host numpy: the COO triple
  and the labels); both batches are built by ``SparseLabeledPointBatch.
  from_coo(..., hybrid=HybridPolicy(hot_cols=...))``, which splits on the host
  and places (span ``layout``);
- the ``OptimizerConfig`` is built from every key the configuration's
  ``optimizer`` gives;
- ``counters()`` hands the per-layer readers each traced episode's objective
  evaluations, the tail's entries (padding apart), and the data's shape.
"""

from __future__ import annotations

import os
import time

from benchmark.manifest import HERE, load_module

base = load_module(os.path.join(HERE, "drivers", "glm_path.py"))


def layout_gauges(label: str) -> dict:
    """The layout decision ``_hybrid_arrays`` recorded under
    ``layout/<label>/*`` (telemetry/layout.py), by its own names."""
    from photon_ml_tpu.telemetry.registry import default_registry

    prefix = f"layout/{label}/"
    gauges = default_registry().snapshot()["gauges"]
    return {name[len(prefix):]: value for name, value in gauges.items()
            if name.startswith(prefix)}


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans):
        import jax
        import jax.numpy as jnp

        # a program whose model cannot score a sparse block fails HERE, before
        # a second is spent generating: the episode needs `sparse_product`
        from photon_ml_tpu.data.sparse_batch import (
            HybridPolicy,
            SparseLabeledPointBatch,
            sparse_product,  # noqa: F401
        )

        from benchmark import datagen_sparse
        from benchmark.manifest import layer_metric_reader
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
        from photon_ml_tpu.types import TaskType

        self.config, self.traffic, self.spans = config, traffic, spans
        self.devices = list(devices)
        home = self.devices[0]
        with spans.span("generate"):
            self.data = datagen_sparse.make_sparse(config, seed)
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
            config["feature_dtype"]]
        d, hot_cols = int(config["features"]), int(config["hot_cols"])
        data = self.data

        def build(suffix: str, label: str):
            return SparseLabeledPointBatch.from_coo(
                data["rows" + suffix], data["cols" + suffix], data["vals" + suffix],
                data["y" + suffix], dim=d, dtype=dtype,
                hybrid=HybridPolicy(hot_cols=hot_cols, label=label))

        with spans.span("layout"), jax.default_device(home):
            self.batch = build("", "bench_train")
            self.val_features = build("_val", "bench_validation")
            jax.block_until_ready((self.batch, self.val_features))
        self.layout = layout_gauges("bench_train")
        print("layout: " + " ".join(f"{k}={v:g}" for k, v in self.layout.items())
              + f" ell={tuple(self.batch.ell_vals.shape)} flat={self.batch.nnz}",
              flush=True)
        opt = dict(config["optimizer"])
        self.optimizer = OptimizerConfig(
            optimizer_type=OptimizerType[opt.pop("type")], **opt)
        self.task = TaskType[config["task"]]
        self.lambdas = [float(lam) for lam in config["lambdas"]]
        trace_lower_s = layer_metric_reader("trace_lower_s")
        self._retrace_seconds = lambda: trace_lower_s({})
        self.read_counters = False  # the harness sets it on traced runs
        self.retrace_s: list[tuple[float, float]] = []  # (episode start, seconds)
        self.evaluations: list[tuple[float, int]] = []  # (episode start, count)
        self.last = None

    # -- the timed path: glm_path's, with the traced runs' count kept ---------

    def episode(self):
        started = time.perf_counter()
        last = super().episode()
        if self.read_counters:
            self.evaluations.append((started, sum(last["evaluations"])))
        return last

    def counters(self) -> dict:
        return {**super().counters(), "evaluations": self.evaluations,
                "tail_entries": int(self.layout["tail_nnz"]),
                "k_hot": int(self.layout["k_hot"]),
                # (entries, rows, features) of the training rows
                "sparse_shape": (len(self.data["vals"]), int(self.config["rows"]),
                                 int(self.config["features"]))}
