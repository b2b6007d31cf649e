"""Traffic kind ``game_sweeps_x4``: the fits of ``game_sweeps``, on a data set
that no single chip of the mesh can hold.

The episode, the end-to-end metric, the kept rows and the comparisons are
``drivers/game_sweeps.py``'s ``Cell``'s own. What differs is set-up: that
driver commits every array to ``devices[0]`` and lets an episode's placement
lay them out; here the generator's HOST arrays go shard by shard straight to
the chips of the configuration's mesh (``parallel.mesh.shard_game_dataset``),
the buckets are packed for that mesh (lane counts a multiple of its "data"
axis, every block sent lane range by lane range), and the reference is handed
all the devices. An episode's placement then finds everything laid out and
moves nothing. At ``mesh.data`` 1 this is the same fit as ``game_sweeps``.
"""

from __future__ import annotations

import inspect
import os

import numpy as np

from benchmark.manifest import HERE, load_module

base = load_module(os.path.join(HERE, "drivers", "game_sweeps.py"))
FEATURE_SHARDS, RE_COORDINATES = base.FEATURE_SHARDS, base.RE_COORDINATES


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` (inherited) is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans,
                 data: "dict | None" = None):
        import ml_dtypes

        from benchmark import datagen
        from photon_ml_tpu.data.game_data import (
            GameDataset,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.evaluation.evaluators import (
            EvaluationData,
            parse_evaluator,
        )
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
        from photon_ml_tpu.parallel.distributed import (
            FixedEffectStepSpec,
            GameTrainProgram,
            RandomEffectStepSpec,
        )
        from photon_ml_tpu.parallel.mesh import make_mesh, shard_game_dataset
        from photon_ml_tpu.types import TaskType

        if "mesh" not in inspect.signature(build_random_effect_dataset).parameters:
            # a program from before PR 27: said at once, before 26 GB are made
            raise SystemExit(
                "this cell needs buckets packed for a mesh "
                "(build_random_effect_dataset(mesh=...)); this program has none")
        self.config, self.traffic, self.spans = config, traffic, spans
        self.devices = list(devices)
        mesh_shape = config["mesh"]
        self.mesh = make_mesh(int(mesh_shape["data"]), int(mesh_shape["model"]),
                              devices=self.devices)
        with spans.span("generate"):
            # a chunk's draws depend on (data_seed, stream, chunk) only: the
            # arrays are the same whatever the thread count, and a four-chip
            # host has the cores
            datagen.GEN_THREADS = max(datagen.GEN_THREADS, (os.cpu_count() or 1) - 4)
            self.data = data if data is not None else datagen.make_glmix(config, seed)
        dtype = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[
            config["feature_dtype"]]
        ladder = tuple(int(c) for c in config["bucket_ladder"])

        def dataset_of(split: dict) -> GameDataset:
            """The generator's arrays as the program's data set, resident on
            the mesh: every chip is sent its own rows and nothing else.
            Entity ids are handed over as the indices they already are
            (``game_sweeps.py`` says why)."""
            n = len(split["y"])
            host = {
                "labels": split["y"], "offsets": np.zeros(n, np.float32),
                "weights": np.ones(n, np.float32),
                **{f"shard/{k}": split[v].astype(dtype, copy=False)
                   for k, v in FEATURE_SHARDS.items()},
                "entity_idx/user": split["user"], "entity_idx/item": split["item"],
            }
            return shard_game_dataset(GameDataset(
                unique_ids=np.arange(n, dtype=np.int64),
                labels=host["labels"], offsets=host["offsets"],
                weights=host["weights"],
                feature_shards={k: host[f"shard/{k}"] for k in FEATURE_SHARDS},
                entity_idx={t: host[f"entity_idx/{t}"] for t, _ in RE_COORDINATES},
                entity_vocabs={t: np.arange(int(config[t + "s"]["count"])).astype(str)
                               for t, _ in RE_COORDINATES},
                host_cache=host,
            ), self.mesh)

        with spans.span("assemble"):
            self.dataset = dataset_of(self.data["train"])
            self.validation = dataset_of(self.data["validation"])
        with spans.span("pack"):
            self.re_datasets = {
                re_type: build_random_effect_dataset(
                    self.dataset, re_type, shard, bucket_sizes=ladder,
                    mesh=self.mesh)
                for re_type, shard in RE_COORDINATES}
        self.validation_eval = EvaluationData(
            labels=self.validation.host_array("labels"),
            offsets=self.validation.host_array("offsets"),
            weights=self.validation.host_array("weights"),
        )
        self.evaluators = [parse_evaluator("AUC")]
        opt = config["optimizer"]
        optimizer = OptimizerConfig(
            optimizer_type=OptimizerType[opt["type"]],
            max_iterations=int(opt["max_iterations"]),
            rel_function_tolerance=opt.get("rel_function_tolerance"))
        l2 = float(config["l2_weight"])
        outer = self

        class SpannedProgram(GameTrainProgram):
            """The program, with the benchmark's spans round the calls that
            ``train_distributed`` makes into it."""

            def step(self, data, buckets, state):
                if outer.spans.is_open("place"):
                    outer.spans.end("place")
                outer.spans.begin("sweep")
                return super().step(data, buckets, state)

        self.program = SpannedProgram(
            TaskType[config["task"]],
            FixedEffectStepSpec("global", optimizer, l2_weight=l2),
            tuple(RandomEffectStepSpec(re_type, shard, optimizer, l2_weight=l2)
                  for re_type, shard in RE_COORDINATES),
            use_pallas_fe=None,  # the auto rule, as the training driver leaves it
            mesh=self.mesh,
        )
        self.sweeps = int(config["coordinate_descent_iterations"])
        self.rows_per_episode = int(config["rows"]) * self.sweeps
        self.read_counters = False  # the harness sets it; nothing here costs a read
        self.last = self._state = None
