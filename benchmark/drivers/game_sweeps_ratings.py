"""Traffic kind ``game_sweeps_ratings``: the fits of ``game_sweeps`` for a
RATINGS model: squared loss, a fixed effect and THREE random effects (per
user, per song, per artist, the last two over one feature block), every
coordinate's optimizer ``AUTO`` (which the program resolves once, at its
build: L-BFGS for the fixed effect, batched NEWTON for the three random
effects' lanes) and the validation rows' RMSE, lower is better, after every
sweep.

The episode is ``drivers/game_sweeps.py``'s, one full fit on packed buckets:
``train_distributed`` places the inputs, runs the configuration's sweeps of
the ONE fused step from the zero state with the validation split scored after
every sweep, and the episode ends when the final state (the fixed effect and
three tables) has been read back to the host. Set-up is that driver's with
the data from ``datagen_ratings`` and three coordinates packed.

A program from before the Newton counts (``optim/common.SOLVER_COUNT_NAMES``
without ``newton_lockstep_rounds``) is refused AT ONCE, before any data is
made: its Newton has no stop at the float's floor, a ridge lane that sits at
its minimum after one step runs ``max_iterations`` rounds with its bucket, and
the cell's per-layer metrics have nothing to read.

What ``correct`` compares (PERF.md 2): kind (a), at the program's OWN final
state against float64 numpy on the generator's rows, the last sweep's training
loss, the validation margins, the last sweep's validation RMSE and the
relative residual of the LAST coordinate's lanes in their own ridge systems
(the Newton step's own arithmetic: what a Hessian in lower precision moves);
kind (b),
against the reference's own fit (``references/game-ymusic-r2.py``: every
block solved exactly as the ridge problem it is, on the rows the packer
kept), each sweep's loss and validation RMSE, every coordinate's coefficients
and their norms.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.manifest import HERE, load_module

base = load_module(os.path.join(HERE, "drivers", "game_sweeps.py"))

FEATURE_SHARDS = {"global": "x_global", "per_user": "x_user", "per_item": "x_item"}
#: (coordinate, the feature shard it reads), in the order of update; the
#: configuration's block of a coordinate is ``<coordinate>s``
RE_COORDINATES = (("user", "per_user"), ("song", "per_item"), ("artist", "per_item"))
COUNT_THE_CELL_NEEDS = "newton_lockstep_rounds"


def refuse_a_program_without_the_counts() -> None:
    from photon_ml_tpu.optim.common import SOLVER_COUNT_NAMES

    if COUNT_THE_CELL_NEEDS not in SOLVER_COUNT_NAMES:
        raise SystemExit(
            f"this program reports no {COUNT_THE_CELL_NEEDS} "
            "(optim/common.SOLVER_COUNT_NAMES): its Newton has no stop at the "
            "float's floor and the cell game-ymusic-r2.sweeps cannot be read "
            "on it; refusing before any data is made")


def ratings_comparisons(produced: dict, expected: dict, limits: dict) -> list:
    """Against the reference's own fit: per sweep the training loss and the
    validation RMSE; the final coefficients of every coordinate; how far the
    fit moved from the zero state it starts in (a step that returns its state
    unchanged reads 1)."""
    from benchmark.compare import rel_gap, rel_l2

    out = []
    for k, (a, b) in enumerate(zip(produced["losses"], expected["losses"]), 1):
        out.append((f"loss_sweep{k}_rel_gap", rel_gap(a, b), limits["loss_rel_gap"]))
    for k, (a, b) in enumerate(zip(produced["val_rmse"], expected["val_rmse"]), 1):
        out.append((f"val_rmse_sweep{k}_gap", abs(a - b), limits["val_rmse_gap"]))
    if len(produced["losses"]) != len(expected["losses"]):
        out.append(("sweeps_missing", 1.0, 0.0))
    for name in ("fe",) + tuple(k for k, _ in RE_COORDINATES):
        out.append((f"{name}_coef_rel_l2", rel_l2(produced[name], expected[name]),
                    limits[f"{name}_coef_rel_l2"]))
        moved = np.linalg.norm(np.asarray(produced[name], np.float64))
        ref = np.linalg.norm(np.asarray(expected[name], np.float64))
        out.append((f"{name}_norm_rel_gap", abs(moved - ref) / ref,
                    limits["norm_rel_gap"]))
    return out


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` is the timed path. The base's
    set-up is written for two coordinates, AUC and Bernoulli labels, so this
    one is its own (the base's is not called); ``end_to_end``,
    ``validation_margins`` and ``release`` are the base's."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans,
                 data: "dict | None" = None):
        refuse_a_program_without_the_counts()
        import jax
        import ml_dtypes

        from benchmark import datagen_ratings
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
            self.data = (data if data is not None
                         else datagen_ratings.make_ratings(config, seed))
        dtype = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[
            config["feature_dtype"]]
        ladder = tuple(int(c) for c in config["bucket_ladder"])
        home = self.devices[0]  # the arrays are resident there once assembled

        def dataset_of(split: dict) -> GameDataset:
            """The generator's arrays as the program's data set, entity ids
            handed over as the indices they already are (the base's way)."""
            n = len(split["y"])
            host = {
                "labels": split["y"], "offsets": np.zeros(n, np.float32),
                "weights": np.ones(n, np.float32),
                **{f"shard/{k}": split[v].astype(dtype, copy=False)
                   for k, v in FEATURE_SHARDS.items()},
                **{f"entity_idx/{t}": split[t] for t, _ in RE_COORDINATES},
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
        self.evaluators = [parse_evaluator(config["evaluator"])]
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
        resolved = {"global": self.program.fe.optimizer.optimizer_type.name,
                    **{s.re_type: s.optimizer.optimizer_type.name
                       for s in self.program.re_specs}}
        print(f"optimizers as the program resolved {opt['type']}: {resolved}",
              flush=True)
        #: every bucket's [e, cap, d] block and its items' size, for the
        #: Hessian pass's roofline (``benchmark/roofline_newton.py``)
        self.newton_buckets = [
            tuple(int(x) for x in b.features.shape) + (b.features.dtype.itemsize,)
            for spec in self.program.re_specs
            if spec.optimizer.optimizer_type.name == "NEWTON"
            for b in self.re_datasets[spec.re_type].buckets]
        print("buckets (lanes x cap) by coordinate: " + "; ".join(
            f"{t} " + " ".join(f"{b.features.shape[0]}x{b.features.shape[1]}"
                               for b in self.re_datasets[t].buckets)
            for t, _ in RE_COORDINATES), flush=True)
        self.sweeps = int(config["coordinate_descent_iterations"])
        self.rows_per_episode = int(config["rows"]) * self.sweeps
        self.read_counters = False  # the harness sets it; nothing here costs a read
        self.last = self._state = None

    # -- the timed path ------------------------------------------------------

    def episode(self):
        from photon_ml_tpu.parallel.distributed import train_distributed

        spans = self.spans
        metric = "validate:" + self.evaluators[0].name
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
                state = {"fe": np.asarray(result.state.fe_coefficients),
                         **{t: np.asarray(result.state.re_tables[t])
                            for t, _ in RE_COORDINATES}}
        self._state = result.state  # on the device, for validation_margins
        self.last = {
            "losses": [float(x) for x in result.losses],
            "val_rmse": [float(h[metric]) for h in result.metric_history],
            # the metric of the state a model selection keeps: lower is better
            "best_rmse": float(result.best_metric),
            **state,
        }
        return self.last

    def counters(self) -> dict:
        return {"newton_buckets": self.newton_buckets}

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

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """[(name, value, limit)]: every number compared, beside its limit
        (``fit`` False, the readings tool's: only those that need no fit of
        the reference's own)."""
        from benchmark.compare import own_coefficient_comparisons

        limits = self.config["limits"]
        produced = {**produced, "val_margin": self.validation_margins()}
        kept = self.kept_rows()
        print("rows the packer kept: " + " ".join(
            f"{k}={int(v.sum())}" for k, v in kept.items())
            + f"; validation RMSE by sweep "
            + " ".join(f"{v:.6f}" for v in produced["val_rmse"])
            + f", kept as best {produced['best_rmse']:.6f}", flush=True)
        self.release()
        evaluated = reference.evaluate(self.data, produced, kept,
                                       float(self.config["l2_weight"]))
        own = own_coefficient_comparisons(produced, evaluated, limits) + [
            ("val_rmse_own_coef_gap",
             abs(produced["val_rmse"][-1] - evaluated["val_rmse"]),
             limits["val_rmse_own_coef_gap"]),
            # the last coordinate's lanes against their own ridge systems: the
            # Newton step's arithmetic (its Hessian's precision), nothing else
            (f"{RE_COORDINATES[-1][0]}_ridge_own_coef_residual",
             evaluated["last_block_residual"],
             limits[f"{RE_COORDINATES[-1][0]}_ridge_own_coef_residual"]),
            # the selection reads "lower is better": the state kept as best is
            # the sweep of the LOWEST validation RMSE
            ("best_rmse_gap", abs(produced["best_rmse"] - min(produced["val_rmse"])),
             0.0)]
        if not fit:
            return own
        expected = reference.fit(self.data, self.config, kept, self.devices)
        return own + ratings_comparisons(produced, expected, limits)
