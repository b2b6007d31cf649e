"""Traffic kind ``game_sweeps_mf``: the fits of ``game_sweeps`` with a matrix-
factorization coordinate beside the fixed effect and both random effects.

The episode is ``drivers/game_sweeps.py``'s, one full GAME fit on packed
buckets: ``train_distributed`` places the inputs, runs the configuration's
sweeps of the ONE fused step (fixed effect, users, items, the
factorization's row side, its column side) from the zero state and the
seeded starting factors with the validation split scored after every sweep,
and the episode ends when the final state, BOTH FACTOR TABLES INCLUDED, has
been read back to the host. Set-up adds to that driver's: the data come from
``datagen_mf`` (the GLMix arrays with the interaction added to the labels'
margin), the factorization's two bucket sets are packed (span ``pack``,
with the random effects') and made resident like the rest, and the program
is built with the coordinate's spec.

The starting factors are data, the generator's (``datagen_mf.start_factors``:
``init_factors``' rule drawn in numpy by an entity's size RANK; ``--seed``
names the entities, and laid out by id every seed would start another fit),
handed to every episode as its starting state and to the reference's fit:
the program draws none of what it is compared on.

What ``correct`` adds (the objective is bilinear, any rotation of both
tables scores alike, so factors are never compared): the SCORE vectors
``p_u . q_i`` on the validation rows and on a fixed 500,000 training rows
against the reference's own fit, and their norm.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.manifest import HERE, load_module, reader_file

base = load_module(os.path.join(HERE, "drivers", "game_sweeps.py"))
#: the coordinate's name in the program (the packer's default for these two
#: sides): its scope ``mf/<name>/<side>``, its gauges ``mf/<name>/*_pad_fraction``
MF_NAME = "user_x_item"
#: the training rows whose factorization scores are compared: evenly spaced
SCORED_TRAIN_ROWS = 500_000


def scored_train_rows(n: int) -> np.ndarray:
    """The training rows whose factorization scores are compared: evenly
    spaced, half a stride off the rows whose (user, item) pairs the
    validation split rates (``datagen.make_glmix``'s ``pick``)."""
    m = min(SCORED_TRAIN_ROWS, n)
    return ((2 * np.arange(m, dtype=np.int64) + 1) * n) // (2 * m)


def mf_score_comparisons(mf_scores, produced: dict, expected: dict, data: dict,
                         limits: dict) -> list:
    """The factorization against the reference's own fit, by its SCORES in
    float64: the relative L2 gap of ``p_u . q_i`` on the validation rows and
    on fixed training rows (a dropped coordinate reads 1, factors returned
    at their start about 1), and the gap of the validation scores' norms.
    ``mf_scores(split, coefficients, rows)`` is the reference's, for both."""
    from benchmark.compare import rel_l2

    rows = scored_train_rows(len(data["train"]["y"]))
    val = [mf_scores(data["validation"], side) for side in (produced, expected)]
    train = [mf_scores(data["train"], side, rows) for side in (produced, expected)]
    norms = [np.linalg.norm(v) for v in val]
    return [
        ("mf_val_score_rel_l2", rel_l2(*val), limits["mf_val_score_rel_l2"]),
        ("mf_train_score_rel_l2", rel_l2(*train), limits["mf_train_score_rel_l2"]),
        ("mf_score_norm_rel_gap", float(abs(norms[0] - norms[1]) / norms[1]),
         limits["mf_score_norm_rel_gap"]),
    ]


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans,
                 data: "dict | None" = None):
        import jax

        from benchmark import datagen_mf
        from photon_ml_tpu.algorithm.mf_coordinate import (
            MFSideBucket,
            build_mf_dataset,
        )
        from photon_ml_tpu.parallel.distributed import (
            GameTrainState,
            MatrixFactorizationStepSpec,
        )

        if data is None:
            with spans.span("generate_mf"):
                data = datagen_mf.make_game(config, seed)
        super().__init__(config, traffic, seed, devices, spans, data=data)
        mf = config["mf"]
        home = self.devices[0]
        with spans.span("pack"):
            packed = build_mf_dataset(
                self.dataset, mf["row"], mf["col"],
                bucket_sizes=tuple(int(c) for c in config["bucket_ladder"]))
            # resident on the chip like the random effects' blocks: an
            # episode's placement only lays them out
            for side in (packed.row_buckets, packed.col_buckets):
                side[:] = [MFSideBucket(*jax.device_put(
                    (b.labels, b.weights, b.entity_rows, b.sample_rows), home))
                    for b in side]  # (by hand: the parent's buckets have no ``placed``)
        self.mf_datasets = {MF_NAME: packed}
        glmix = self.program  # the base's: the same specs, and its spans
        self.program = type(glmix)(
            glmix.task, glmix.fe, glmix.re_specs,
            mf_specs=(MatrixFactorizationStepSpec(
                MF_NAME, mf["row"], mf["col"], int(mf["latent_factors"]),
                glmix.fe.optimizer, l2_weight=float(mf["l2_weight"]),
                num_alternations=int(mf["alternations"])),),
            use_pallas_fe=None, mesh=self.mesh)
        # the state every episode starts from: zeros, and the generator's
        # starting factors
        self.start_factors = data["start_factors"]
        with jax.default_device(home):
            zero = self.program.init_state(
                self.dataset, self.re_datasets, self.mf_datasets)
        rows, cols = jax.device_put(
            (self.start_factors["mf_" + mf["row"]],
             self.start_factors["mf_" + mf["col"]]), home)
        self.start_state = GameTrainState(
            fe_coefficients=zero.fe_coefficients, re_tables=zero.re_tables,
            mf_rows={MF_NAME: rows}, mf_cols={MF_NAME: cols})

    # -- the timed path ------------------------------------------------------

    def episode(self):
        from photon_ml_tpu.parallel.distributed import train_distributed

        spans = self.spans
        with spans.span("episode"):
            spans.begin("place")
            result = train_distributed(
                self.program, self.dataset, self.re_datasets,
                mf_datasets=self.mf_datasets, mesh=self.mesh,
                num_iterations=self.sweeps, state=self.start_state,
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
                    "mf_user": np.asarray(result.state.mf_rows[MF_NAME]),
                    "mf_item": np.asarray(result.state.mf_cols[MF_NAME]),
                }
        self._state = result.state  # on the device, for validation_margins
        self.last = {
            "losses": [float(x) for x in result.losses],
            "val_auc": [float(h["validate:AUC"]) for h in result.metric_history],
            **state,
        }
        return self.last

    # -- after the window ----------------------------------------------------

    def counters(self) -> dict:
        """Traced runs only (it lowers the step again; the executable comes
        from the compile cache): names and signatures of the compiled step's
        instructions that carry the coordinate's scope, which the profiler's
        events do not, and which of them are the step's own loops
        (``layer_metrics/mf_time_share_pct.py``, which holds the trace's
        events to both)."""
        if not self.read_counters:
            return {}
        from photon_ml_tpu.parallel.multihost import default_put

        data, buckets = self.program.prepare_inputs(
            self.dataset, self.re_datasets, self.mf_datasets)
        data, buckets, state = self.program.shard_inputs(
            self.mesh, data, buckets, self.start_state, put_fn=default_put())
        text = self.program._step.lower(data, buckets, state).compile().as_text()
        scoped, loops = load_module(
            reader_file("mf_time_share_pct")).scoped_instructions(text or "")
        return {"mf_scoped_instructions": scoped, "mf_scoped_loops": loops}

    def kept_rows(self) -> dict:
        """The base's masks, for the factorization's sides too (``mf_user``,
        ``mf_item``): the same ladder caps an entity's rows, so each side
        must have kept what the random effect of its entity kept. The
        factorization's own packed buckets are NOT read: had its packer kept
        other rows, the reference trains on these and the scores part."""
        kept = super().kept_rows()
        mf = self.config["mf"]
        for side in (mf["row"], mf["col"]):
            kept["mf_" + side] = kept[side]
        return kept

    def release(self) -> None:
        super().release()
        self.mf_datasets = self.start_state = None

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """[(name, value, limit)]: the base's numbers with the factorization
        in every margin, and the factorization's scores against the
        reference's own fit."""
        from benchmark.compare import glmix_comparisons, own_coefficient_comparisons

        limits = self.config["limits"]
        produced = {**produced, "val_margin": self.validation_margins()}
        kept = self.kept_rows()
        self.release()
        own = own_coefficient_comparisons(
            produced, reference.evaluate(self.data, produced), limits)
        if not fit:
            return own
        expected = reference.fit(self.data, self.config, kept, self.devices,
                                 self.start_factors)
        return (own + glmix_comparisons(produced, expected, limits)
                + mf_score_comparisons(reference.mf_scores, produced, expected,
                                       self.data, limits))
