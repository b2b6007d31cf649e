"""Traffic kind ``glm_grid``: the fits of ``glm_path`` with the whole λ grid
fitted AT ONCE, as vmapped solver lanes (``estimators.train_glm_grid``, what
``cli/glm_driver.py --grid-parallel`` calls), under an elastic net.

The episode is ``drivers/glm_path.py``'s with the call changed: one call of
``train_glm_grid`` on the placed batch (the configuration's optimizer built
key for key, its ``lambdas`` as ``regularization_weights``, its
``elastic_net_alpha``, a recorder as ``telemetry``), every λ's model scoring
the resident validation block, ended by a host read of the ``[lanes, d]``
coefficients and the ``[lanes, n_val]`` margins. What differs besides:

- the episode's two reads are ONE each: the lanes' coefficient vectors and
  their margins are stacked on the device and read whole (a read a lane, as
  the path cells make a read a λ, was 200 reads and 0.16 s of host in a
  1.34 s episode, and ``fit_s`` moved with the host's level from process to
  process: PERF.md 6, PR 47). The scorings stay one dispatch a lane;

- the recorder keeps the lanes' ``SolverResult`` stack as ``record_lanes`` is
  handed it, on the device; the episode reads its values, pseudo-gradient
  norms, iterations, reasons and trial counts (small arrays);
- after the window the run prints one ``lanes:`` line (for each lane:
  iterations, its own evaluations, floor exits, reason, non-zeros);
- ``counters()`` hands the per-layer readers each traced episode's LOCK-STEP
  evaluations (what the device ran: one at the start, then trip by trip the
  slowest live lane's trials), the lanes' OWN evaluations summed, and the
  operand's shape;
- ``correct`` is judged by quarter of the grid (``benchmark/compare_grid.py``).

The cell needs a program whose OWL-QN keeps stopped lanes out of the block's
search loop and ends a search at the float's floor (PR 47): without the first
rule a fit takes thirty evaluations of the whole block in every outer trip,
and a run does not end inside its time. Such a program is refused at once.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.manifest import HERE, load_module

base = load_module(os.path.join(HERE, "drivers", "glm_path.py"))
tron = load_module(os.path.join(HERE, "drivers", "glm_path_tron.py"))


class LaneRecorder:
    """What ``train_glm_grid`` hands its ``telemetry``: the lanes' stacked
    ``SolverResult`` and their λ, kept as they are; nothing is read here."""

    def __init__(self):
        self.lambdas: list[float] = []
        self.result = None

    def record_lanes(self, _coordinate, result, *, keys=None, **_):
        self.lambdas = [float(key["lambda"]) for key in keys]
        self.result = result
        return {}


def lockstep_evaluations(trials: np.ndarray) -> int:
    """Evaluations of the WHOLE lane block in one fit, from the lanes'
    ``line_search_trials`` ``[lanes, max_iter + 1]``: one at the shared start,
    then in every outer trip as many as the slowest live lane's search asks (a
    lane past its last iteration holds zeros)."""
    return 1 + int(np.sum(np.max(trials, axis=0)))


def own_evaluations(trials: np.ndarray) -> np.ndarray:
    """[lanes]: what each lane's solve asked for by itself."""
    return 1 + np.sum(trials, axis=1)


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans):
        from photon_ml_tpu.optim import common

        if not hasattr(common, "at_line_search_floor"):
            raise SystemExit(
                "this program's OWL-QN has neither the stopped-lane rule nor the "
                "floor (optim/owlqn.py, PR 47): the cell would not end inside a "
                "run's time; refusing to measure")
        super().__init__(config, traffic, seed, devices, spans)
        self.optimizer = tron.optimizer_config(config["optimizer"])
        self.alpha = float(config["elastic_net_alpha"])
        self.evaluations: list[tuple[float, int, int]] = []  # (start, lock-step, own)

    # -- the timed path ------------------------------------------------------

    def episode(self):
        import jax.numpy as jnp

        from photon_ml_tpu.estimators import train_glm_grid

        started = time.perf_counter()
        before = self._retrace_seconds() if self.read_counters else None
        with self.spans.span("episode"):
            recorder = LaneRecorder()
            models = train_glm_grid(
                self.batch, self.task, optimizer=self.optimizer,
                regularization_weights=self.lambdas,
                elastic_net_alpha=self.alpha, telemetry=recorder)
            with self.spans.span("score"):  # dispatched, read below
                margins = [models[lam].score(self.val_features)
                           for lam in self.lambdas]
            with self.spans.span("read"):
                result = recorder.result
                # the lanes lie in ascending λ, the configuration's list as
                # glmnet writes it, largest first
                lane = {lam: k for k, lam in enumerate(recorder.lambdas)}
                order = np.asarray([lane[lam] for lam in self.lambdas])
                trials = np.asarray(result.line_search_trials)[order]
                self.last = {
                    "lambdas": self.lambdas,
                    "l1_weights": [self.alpha * lam for lam in self.lambdas],
                    "coefficients": np.asarray(jnp.stack([
                        models[lam].coefficients.means for lam in self.lambdas])),
                    "val_margin": np.asarray(jnp.stack(margins)).astype(np.float32),
                    "values": np.asarray(result.value)[order].tolist(),
                    "gradient_norms": np.asarray(result.gradient_norm)[order].tolist(),
                    "iterations": np.asarray(result.iterations)[order].tolist(),
                    "reasons": np.asarray(result.reason)[order].tolist(),
                    "floor_exits": np.asarray(result.floor_exits)[order].tolist(),
                    "evaluations": own_evaluations(trials).tolist(),
                    "lockstep_evaluations": lockstep_evaluations(trials),
                }
        if before is not None:
            self.retrace_s.append((started, self._retrace_seconds() - before))
            self.evaluations.append((started, self.last["lockstep_evaluations"],
                                     int(sum(self.last["evaluations"]))))
        return self.last

    def counters(self) -> dict:
        rows, features = self.batch.features.shape
        return {**super().counters(), "grid_evaluations": self.evaluations,
                "grid_operand": (int(rows), int(features),
                                 int(self.batch.features.dtype.itemsize),
                                 len(self.lambdas))}

    # -- after the window ----------------------------------------------------

    def lane_lines(self, produced: dict) -> str:
        from photon_ml_tpu.optim.common import ConvergenceReason

        lanes = zip(produced["lambdas"], produced["iterations"],
                    produced["evaluations"], produced["floor_exits"],
                    produced["reasons"], np.count_nonzero(produced["coefficients"], axis=1))
        return (f"lanes: lock-step evaluations {produced['lockstep_evaluations']} "
                f"own {sum(produced['evaluations'])} of {len(produced['lambdas'])} lanes; "
                "lambda:iterations/evaluations/floor_exits/reason/nonzeros "
                + " ".join(f"{lam:.6g}:{i}/{e}/{f}/{ConvergenceReason(r).name}/{nz}"
                           for lam, i, e, f, r, nz in lanes))

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """[(name, value, limit)]: every number compared, beside its limit
        (``fit`` False, the readings tool's: only kind (a), which needs no
        fit of the reference's own)."""
        from benchmark.compare_grid import (
            minimizer_comparisons,
            own_coefficient_comparisons,
        )

        limits = self.config["limits"]
        data = self.host_data()
        self.release()
        print(self.lane_lines(produced), flush=True)
        if not fit:
            return own_coefficient_comparisons(produced, reference.evaluate(
                data, produced["coefficients"], produced["lambdas"], self.alpha), limits)
        lambdas = [float(lam) for lam in self.config["lambdas"]]
        exact = reference.fit(data, self.config, self.devices)
        # one float64 pass over the rows for both sets of coefficient vectors
        both = reference.evaluate(
            data, np.concatenate([produced["coefficients"], exact]),
            list(produced["lambdas"]) + lambdas, self.alpha)
        k = len(produced["coefficients"])
        own = {name: values[:k] for name, values in both.items()}
        expected = {"coefficients": exact, "lambdas": lambdas,
                    **{name: values[k:] for name, values in both.items()}}
        return (own_coefficient_comparisons(produced, own, limits)
                + minimizer_comparisons(produced, expected, data["y_val"], limits))
