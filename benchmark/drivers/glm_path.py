"""Traffic kind ``glm_path``: fits of one dense GLM over a λ grid, back to
back, on a data set that is resident on the chip.

One episode is one round of model selection: one call of
``photon_ml_tpu.estimators.train_glm`` (what ``cli/glm_driver.py`` calls;
``use_pallas`` left to its auto rule) on the placed ``LabeledPointBatch``,
every λ of the configuration as ONE warm-started path from zero; then every
λ's model scores the resident validation block (``GeneralizedLinearModel.
score``, what a selection over the grid needs); ended by a host read of the
coefficient vectors and those margins. ``correct`` compares what the episode
itself read, nothing scored after the window. Placing X and the validation
block is set-up: a resident data set is fitted many times, and the traffic
file says so.
"""

from __future__ import annotations

import time

import numpy as np


class SolveRecorder:
    """What ``train_glm`` hands its ``telemetry``: every λ's ``SolverResult``
    is kept as it is, on the device; nothing is read here."""

    def __init__(self):
        self.solves: list = []  # (lambda, SolverResult)

    def record_solve(self, _coordinate, result, *, extra=None, **_):
        self.solves.append((float(extra["lambda"]), result))
        return {}

    def heartbeat(self, *_args, **_cursor):
        return None


class Cell:
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans):
        import jax
        import jax.numpy as jnp

        from benchmark import datagen_dense
        from benchmark.manifest import layer_metric_reader
        from photon_ml_tpu.data.batch import LabeledPointBatch
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
        from photon_ml_tpu.types import TaskType

        self.config, self.traffic, self.spans = config, traffic, spans
        self.devices = list(devices)
        home = self.devices[0]
        with spans.span("generate"):
            self.data = datagen_dense.make_dense(config, seed, home)
            jax.block_until_ready(self.data)
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
            config["feature_dtype"]]
        with spans.span("batch"), jax.default_device(home):
            # float32: the batch holds the generator's own arrays, no copy
            self.batch = LabeledPointBatch.create(
                self.data["x"], self.data["y"], dtype=dtype)
            self.val_features = jnp.asarray(self.data["x_val"], dtype)
            jax.block_until_ready((self.batch, self.val_features))
        opt = config["optimizer"]
        self.optimizer = OptimizerConfig(
            optimizer_type=OptimizerType[opt["type"]],
            max_iterations=int(opt["max_iterations"]),
            rel_function_tolerance=opt.get("rel_function_tolerance"))
        self.task = TaskType[config["task"]]
        self.lambdas = [float(lam) for lam in config["lambdas"]]
        #: seconds the process has spent tracing and lowering so far, by the
        #: program's compile listener (the reader of `trace_lower_s`)
        trace_lower_s = layer_metric_reader("trace_lower_s")
        self._retrace_seconds = lambda: trace_lower_s({})
        self.read_counters = False  # the harness sets it on traced runs
        self.retrace_s: list[tuple[float, float]] = []  # (episode start, seconds)
        self.last = None

    # -- the timed path ------------------------------------------------------

    def episode(self):
        from photon_ml_tpu.estimators import train_glm

        started = time.perf_counter()
        before = self._retrace_seconds() if self.read_counters else None
        with self.spans.span("episode"):
            recorder = SolveRecorder()
            models = train_glm(
                self.batch, self.task, optimizer=self.optimizer,
                regularization_weights=self.lambdas, telemetry=recorder)
            with self.spans.span("score"):  # dispatched, read below
                margins = [models[lam].score(self.val_features)
                           for lam in self.lambdas]
            with self.spans.span("read"):
                solves = dict(recorder.solves)
                self.last = {
                    "lambdas": self.lambdas,
                    "coefficients": np.stack([
                        np.asarray(models[lam].coefficients.means)
                        for lam in self.lambdas]),
                    "val_margin": np.stack([
                        np.asarray(m) for m in margins]).astype(np.float32),
                    "values": [float(solves[lam].value) for lam in self.lambdas],
                    "gradient_norms": [float(solves[lam].gradient_norm)
                                       for lam in self.lambdas],
                    "iterations": [int(solves[lam].iterations) for lam in self.lambdas],
                    "reasons": [int(solves[lam].reason) for lam in self.lambdas],
                    # one evaluation at the start, then one for each trial point
                    "evaluations": [
                        1 + int(np.sum(np.asarray(solves[lam].line_search_trials)))
                        for lam in self.lambdas],
                }
        if before is not None:
            self.retrace_s.append((started, self._retrace_seconds() - before))
        return self.last

    def end_to_end(self, episode_seconds: list[float], window_seconds: float) -> dict:
        """The window's wall seconds over its whole episodes: a stall inside
        the window moves it."""
        return {"fit_s": (window_seconds / len(episode_seconds), "s")}

    def counters(self) -> dict:
        return {"retrace_s": self.retrace_s}

    # -- after the window ----------------------------------------------------

    def host_data(self) -> dict:
        """The generator's float32 arrays, read back for the reference."""
        return {k: np.asarray(v) for k, v in self.data.items()}

    def release(self) -> None:
        """Drop every device array of the run before the reference runs."""
        self.data = self.batch = self.val_features = None

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """[(name, value, limit)]: every number compared, beside its limit
        (``fit`` False, the readings tool's: only those that need no fit of
        the reference's own)."""
        from benchmark.compare import (
            path_comparisons,
            path_own_coefficient_comparisons,
        )

        limits = self.config["limits"]
        data = self.host_data()
        self.release()
        print("solves: " + " ".join(
            f"lambda{lam:g}: iterations {i} evaluations {e} reason {r}"
            for lam, i, e, r in zip(produced["lambdas"], produced["iterations"],
                                    produced["evaluations"], produced["reasons"])),
              flush=True)
        lambdas = self.config["lambdas"]
        if not fit:
            return path_own_coefficient_comparisons(produced, reference.evaluate(
                data, produced["coefficients"], produced["lambdas"]), limits)
        exact = reference.fit(data, self.config, self.devices)
        # one float64 pass over the rows for both sets of coefficient vectors
        both = reference.evaluate(
            data, np.concatenate([produced["coefficients"], exact]),
            list(produced["lambdas"]) + list(lambdas))
        k = len(produced["coefficients"])
        own = {name: values[:k] for name, values in both.items()}
        expected = {"coefficients": exact,
                    **{name: values[k:] for name, values in both.items()}}
        return (path_own_coefficient_comparisons(produced, own, limits)
                + path_comparisons(produced, expected, data["y_val"], limits))
