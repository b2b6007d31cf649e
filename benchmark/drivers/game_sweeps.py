"""Traffic kind ``game_sweeps``: GLMix fits on packed buckets, back to back.

One episode is one fit as a user's training job runs it once the data is
packed: ``train_distributed`` places the inputs on the mesh, runs the
configuration's coordinate-descent sweeps from a zero state with the
validation split scored after every sweep, and the episode ends when the
final state has been read back to the host.
"""

from __future__ import annotations

import numpy as np

FEATURE_SHARDS = {"global": "x_global", "per_user": "x_user", "per_item": "x_item"}
RE_COORDINATES = (("user", "per_user"), ("item", "per_item"))


class Cell:
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans,
                 data: "dict | None" = None):
        import jax
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
        from photon_ml_tpu.parallel.mesh import make_mesh
        from photon_ml_tpu.types import TaskType

        self.config, self.traffic, self.spans = config, traffic, spans
        self.devices = list(devices)
        mesh_shape = config["mesh"]
        self.mesh = make_mesh(int(mesh_shape["data"]), int(mesh_shape["model"]),
                              devices=self.devices)
        with spans.span("generate"):
            self.data = data if data is not None else datagen.make_glmix(config, seed)
        dtype = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[
            config["feature_dtype"]]

        ladder = tuple(int(c) for c in config["bucket_ladder"])
        # the arrays are resident on the chip once assembled, as the
        # program's own build_game_dataset leaves them; an episode's
        # placement only lays them out
        home = self.devices[0]

        def dataset_of(split: dict) -> GameDataset:
            """The generator's arrays as the program's data set. Entity ids
            are handed over as the indices they already are (vocabulary =
            the ids in order, so table row k is entity k);
            ``build_game_dataset`` would turn 5 M ids into strings and sort
            them to find the same indices (measured 12.6 s of set-up)."""
            n = len(split["y"])
            host = {
                "labels": split["y"], "offsets": np.zeros(n, np.float32),
                "weights": np.ones(n, np.float32),
                **{f"shard/{k}": split[v].astype(dtype, copy=False)
                   for k, v in FEATURE_SHARDS.items()},
                "entity_idx/user": split["user"], "entity_idx/item": split["item"],
            }
            def put(name):  # committed to its home: later slicing happens there
                return jax.device_put(host[name], home)

            return GameDataset(
                unique_ids=np.arange(n, dtype=np.int64),
                labels=put("labels"), offsets=put("offsets"), weights=put("weights"),
                feature_shards={k: put(f"shard/{k}") for k in FEATURE_SHARDS},
                entity_idx={t: put(f"entity_idx/{t}") for t, _ in RE_COORDINATES},
                entity_vocabs={t: np.arange(int(config[t + "s"]["count"])).astype(str)
                               for t, _ in RE_COORDINATES},
                host_cache=host,
            )

        with jax.default_device(home):
            with spans.span("assemble"):
                self.dataset = dataset_of(self.data["train"])
                self.validation = dataset_of(self.data["validation"])
            with spans.span("pack"):
                self.re_datasets = {
                    re_type: build_random_effect_dataset(
                        self.dataset, re_type, shard, bucket_sizes=ladder)
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

    # -- the timed path ------------------------------------------------------

    def episode(self):
        from photon_ml_tpu.parallel.distributed import train_distributed

        spans = self.spans
        with spans.span("episode"):
            spans.begin("place")
            result = train_distributed(
                self.program, self.dataset, self.re_datasets, mesh=self.mesh,
                num_iterations=self.sweeps,
                validation_dataset=self.validation,
                validation_evaluators=self.evaluators,
                validation_eval_data=self.validation_eval,
                on_sweep=lambda done, total, loss: spans.end("sweep"),
            )
            with spans.span("read"):
                state = {
                    "fe": np.asarray(result.state.fe_coefficients),
                    "user": np.asarray(result.state.re_tables["user"]),
                    "item": np.asarray(result.state.re_tables["item"]),
                }
        self._state = result.state  # on the device, for validation_margins
        self.last = {
            "losses": [float(x) for x in result.losses],
            "val_auc": [float(h["validate:AUC"]) for h in result.metric_history],
            **state,
        }
        return self.last

    def end_to_end(self, episode_seconds: list[float], window_seconds: float) -> dict:
        """All the rows trained in the window over all of its wall time."""
        return {"train_rows_per_s": (
            self.rows_per_episode * len(episode_seconds) / window_seconds, "rows/s")}

    def counters(self) -> dict:
        return {}

    # -- after the window ----------------------------------------------------

    def kept_rows(self) -> dict:
        """Per coordinate, the training rows the packer kept (the ladder's
        top rung caps an entity; the reference trains on the same rows)."""
        n = int(self.config["rows"])
        kept = {}
        for re_type, _ in RE_COORDINATES:
            mask = np.zeros(n, bool)
            for bucket in self.re_datasets[re_type].buckets:
                rows = np.asarray(bucket.sample_rows).ravel()
                mask[rows[rows >= 0]] = True
            kept[re_type] = mask
        return kept

    def validation_margins(self) -> np.ndarray:
        """[n_val] margins of the validation split at the last episode's
        final state, from the scoring program the window drove after every
        sweep (same inputs, same placement as train_distributed makes)."""
        from photon_ml_tpu.parallel.multihost import default_put

        val = self.program.prepare_scoring_inputs(self.validation, self.re_datasets)
        val = self.program.shard_scoring_inputs(self.mesh, val, put_fn=default_put())
        scores = np.asarray(self.program.score(val, self._state))
        return scores[: int(self.config["validation_rows"])]

    def release(self) -> None:
        """Drop every device array of the program before the reference runs."""
        self.dataset = self.re_datasets = self.validation = None
        self.program = self._state = None

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """[(name, value, limit)]: every number compared, beside its limit
        (``fit`` False, the readings tool's: only those that need no fit of
        the reference's own)."""
        from benchmark.compare import glmix_comparisons, own_coefficient_comparisons

        limits = self.config["limits"]
        produced = {**produced, "val_margin": self.validation_margins()}
        kept = self.kept_rows()
        self.release()
        own = own_coefficient_comparisons(
            produced, reference.evaluate(self.data, produced), limits)
        if not fit:
            return own
        expected = reference.fit(self.data, self.config, kept, self.devices)
        return own + glmix_comparisons(produced, expected, limits)
