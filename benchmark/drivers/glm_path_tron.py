"""Traffic kind ``glm_path_tron``: the fits of ``glm_path`` under a trust-region
Newton solver (``optim/tron.py``: LIBLINEAR's primal solver for L2 logistic
regression, upstream's second optimizer).

The episode is ``drivers/glm_path.py``'s, line for line: one call of
``estimators.train_glm`` on the placed batch over the configuration's λ grid
as one warm-started path from zero, every λ's model scoring the resident
validation block, ended by a host read of the coefficient vectors and of
those margins. What differs:

- the ``OptimizerConfig`` is built from every key the configuration's
  ``optimizer`` gives (``optimizer_config``): TRON's own stop ``tolerance``
  and its CG cap reach the program, which that driver's three keys cannot
  carry;
- the episode keeps each λ's ``SolverResult`` on the device; after the
  window the last episode's are read and printed by λ: rounds, Hessian-vector
  products (``line_search_trials``, where TRON files a round's CG steps),
  rejected rounds, rounds the float32 floor ended, reason;
- after the window, at the last timed episode's coefficients and before the
  arrays are released, ONE more product a λ: the product the solve would
  take next, ``(X' D X + λ I) g`` with ``g`` the gradient there, through
  ``GLMObjective.hessian_vector`` (the public function the solve's CG calls)
  on the resident batch; ``verify`` holds it against the reference's float64
  product of the same vector (``hv_own_coef_rel_gap``), so that a product in
  lower precision fails by a number and not by a slower convergence. A
  product is no output a user asks for: the episode is not lengthened by it,
  as the GLMix cells make one more call of the scoring program the window
  drove;
- ``counters()`` hands the per-layer readers each traced episode's products
  and the operand's shape.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.manifest import HERE, load_module

base = load_module(os.path.join(HERE, "drivers", "glm_path.py"))


def optimizer_config(opt: dict):
    """The configuration's ``optimizer`` as the program's ``OptimizerConfig``,
    key for key: ``type`` names the ``OptimizerType``, every other key is the
    field of that name (a key the program has no field for is an error, not
    a default)."""
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

    fields = {key: value for key, value in opt.items() if key != "type"}
    return OptimizerConfig(optimizer_type=OptimizerType[opt["type"]], **fields)


def rejected_rounds(values: np.ndarray, gradient_norms: np.ndarray,
                    iterations: int) -> int:
    """Rounds whose step was not kept: the value AND the gradient norm of
    the round repeat the round before (a kept step moves the gradient even
    where float32 leaves the value as it was)."""
    v, g = values[:iterations + 1], gradient_norms[:iterations + 1]
    return int(np.sum((v[1:] == v[:-1]) & (g[1:] == g[:-1])))


def hv_comparisons(produced: np.ndarray, expected: np.ndarray, lambdas,
                   limits: dict) -> list:
    """For each λ the program's product against the reference's float64
    product of the same vector at the same coefficients: relative L2 gap (a
    product taken at other coefficients, or with its operands rounded to
    bfloat16, reads 1e-3 and more)."""
    from benchmark.compare import _limit, rel_l2

    if len(produced) != len(expected):
        return [("hv_lambdas_missing", 1.0, 0.0)]
    return [(f"lambda{lam:g}_hv_own_coef_rel_gap", rel_l2(produced[k], expected[k]),
             _limit(limits, "hv_own_coef_rel_gap", lam))
            for k, lam in enumerate(lambdas)]


class Cell(base.Cell):
    """Set-up state of one run; ``episode`` is the timed path."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices, spans):
        super().__init__(config, traffic, seed, devices, spans)
        self.optimizer = optimizer_config(config["optimizer"])
        self.solves: dict = {}  # λ -> the last episode's SolverResult, on the device
        self.hv_products: list[tuple[float, int]] = []  # (episode start, products)
        self._host = None

    # -- the timed path ------------------------------------------------------

    def episode(self):
        from photon_ml_tpu.estimators import train_glm

        started = time.perf_counter()
        before = self._retrace_seconds() if self.read_counters else None
        with self.spans.span("episode"):
            recorder = base.SolveRecorder()
            models = train_glm(
                self.batch, self.task, optimizer=self.optimizer,
                regularization_weights=self.lambdas, telemetry=recorder)
            with self.spans.span("score"):  # dispatched, read below
                margins = [models[lam].score(self.val_features)
                           for lam in self.lambdas]
            with self.spans.span("read"):
                solves = self.solves = dict(recorder.solves)
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
                    # one evaluation at the start and one a round; the
                    # products are counted apart
                    "evaluations": [1 + int(solves[lam].iterations)
                                    for lam in self.lambdas],
                    "hv_products": [
                        int(np.sum(np.asarray(solves[lam].line_search_trials)))
                        for lam in self.lambdas],
                }
        if before is not None:
            self.retrace_s.append((started, self._retrace_seconds() - before))
            self.hv_products.append((started, sum(self.last["hv_products"])))
        return self.last

    def counters(self) -> dict:
        rows, features = self.batch.features.shape
        return {**super().counters(), "hv_products": self.hv_products,
                "hv_operand": (int(rows), int(features),
                               int(self.batch.features.dtype.itemsize))}

    # -- after the window ----------------------------------------------------

    def host_data(self) -> dict:
        if self._host is None:
            self._host = super().host_data()
        return self._host

    def next_products(self, produced: dict) -> tuple[np.ndarray, np.ndarray]:
        """([k, d] the gradients ``g`` of the λ objectives at the produced
        coefficients, [k, d] the products ``(X' D X + λ I) g``), float32 as
        the program made them: one jitted call a λ on the resident batch."""
        import jax

        from photon_ml_tpu.estimators import loss_for_task
        from photon_ml_tpu.ops.objective import GLMObjective

        objective = GLMObjective(loss_for_task(self.task))

        @jax.jit
        def gradient_and_product(batch, w, lam):
            g = objective.gradient(w, batch) + lam * w
            return g, objective.hessian_vector(w, g, batch) + lam * g

        dtype = self.batch.solve_dtype
        with jax.default_device(self.devices[0]):
            pairs = [gradient_and_product(self.batch, np.asarray(w, dtype),
                                          np.asarray(lam, dtype))
                     for w, lam in zip(produced["coefficients"], produced["lambdas"])]
        return (np.stack([np.asarray(g) for g, _ in pairs]),
                np.stack([np.asarray(hv) for _, hv in pairs]))

    def solve_lines(self) -> str:
        """The last episode's solves by λ, read now (the episode read none of
        their histories)."""
        from photon_ml_tpu.optim.common import ConvergenceReason

        out = []
        for lam, iterations, products, reason in zip(
                self.lambdas, self.last["iterations"], self.last["hv_products"],
                self.last["reasons"]):
            solve = self.solves[lam]
            rejected = rejected_rounds(
                np.asarray(solve.value_history),
                np.asarray(solve.grad_norm_history), iterations)
            out.append(
                f"lambda{lam:g}: rounds {iterations} products {products} rejected "
                f"{rejected} floor_exits {int(solve.floor_exits)} reason "
                f"{ConvergenceReason(reason).name}")
        return "tron solves: " + " ".join(out)

    def release(self) -> None:
        super().release()
        self.solves = {}

    def verify(self, reference, produced: dict,
               fit: bool = True) -> list[tuple[str, float, float]]:
        """``glm_path``'s comparisons, then the products' own."""
        print(self.solve_lines(), flush=True)
        vectors, products = self.next_products(produced)
        comparisons = super().verify(reference, produced, fit)  # releases
        data, self._host = self._host, None
        expected = reference.hessian_vector(
            data, produced["coefficients"], vectors, produced["lambdas"])
        return comparisons + hv_comparisons(
            products, expected, produced["lambdas"], self.config["limits"])
