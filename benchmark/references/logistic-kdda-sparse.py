"""Plain reference for the sparse logistic λ-path, on the flat COO triple
(row, column, value) the generator made: no layout, no kernel, nothing imported
from the program.

``fit``: for each λ a minimizer of ``sum_i logloss(x_i.w, y_i) + λ/2 |w|^2`` by
Newton steps whose linear systems are solved by conjugate gradients with the
Hessian's diagonal as preconditioner (the method LIBLINEAR's primal solver is
built on, without its trust region: a step is halved while it does not decrease
the objective), in float32 ``jax.numpy`` under ``default_matmul_precision(
"highest")``. Margins are a gather and a ``segment_sum`` over the rows
(``num_segments`` given), the gradient and the Hessian-vector product their
transposes (a ``segment_sum`` over the columns). At d of 2e7 a Hessian cannot be
formed and every product is a pass over every entry, so the work is CAPPED
(``NEWTON_STEPS`` x ``CG_STEPS`` a λ) to what a run's clock bears; how far from
the minimizer that ends is read in float64 by ``evaluate`` (the norm of the
gradient at the returned vector, printed: ``|w - w*| <= |g| / λ`` for this
strongly convex objective), and the comparison's limits are set with that
distance in them (PERF.md 2).

``evaluate`` asks what does not turn on how far a solver got: what do GIVEN
coefficient vectors give on the generator's float32 entries? Objective values,
gradient norms and validation margins in float64 numpy on the host
(``np.add.reduceat`` over the sorted rows, ``np.bincount`` with weights over
the columns: no dense d x anything), so that the only rounding in the
comparison is the program's.
"""

from __future__ import annotations

import time

import numpy as np

NEWTON_STEPS = 3  # at most, for each λ
CG_STEPS = 6  # at most, for each Newton step
#: the CG ends early once the residual is this share of the gradient
CG_FORCING = 0.05
#: Newton ends once the gradient is this share of the gradient at zero
GRADIENT_STOP = 1e-4
EPS32 = float(np.finfo(np.float32).eps)


def fit(data: dict, cfg: dict, devices) -> np.ndarray:
    """data: host arrays {"rows", "cols" int32 [nnz], "vals" float32 [nnz],
    "y" float32 [n]}. Returns [len(lambdas), d] float32, in the order of the
    configuration's ``lambdas``, each λ warm-started from the one before."""
    import jax
    import jax.numpy as jnp

    started = time.perf_counter()
    n, d = len(data["y"]), int(cfg["features"])
    put = lambda a: jax.device_put(np.ascontiguousarray(a), devices[0])

    with jax.default_matmul_precision("highest"):
        entries = (put(data["rows"]), put(data["cols"]), put(data["vals"]))
        y = put(data["y"])

        # the arrays are ARGUMENTS of the jitted functions, never constants
        def margins(w, entries):
            rows, cols, vals = entries
            return jax.ops.segment_sum(vals * w[cols], rows, num_segments=n,
                                       indices_are_sorted=True)

        def transpose(r, entries):
            rows, cols, vals = entries
            return jax.ops.segment_sum(vals * r[rows], cols, num_segments=d)

        @jax.jit
        def objective(w, lam, entries, y):
            m = margins(w, entries)
            return jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.5 * lam * jnp.vdot(w, w)

        @jax.jit
        def newton_direction(w, lam, entries, y):
            """(the objective, its gradient's norm, the step ``H^-1 g`` by
            preconditioned CG from zero, the CG steps taken)."""
            rows, cols, vals = entries
            m = margins(w, entries)
            p = jax.nn.sigmoid(m)
            value = jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.5 * lam * jnp.vdot(w, w)
            g = transpose(p - y, entries) + lam * w
            curvature = p * (1.0 - p)
            diagonal = jax.ops.segment_sum(
                vals * vals * curvature[rows], cols, num_segments=d) + lam

            def hv(v):
                return transpose(curvature * margins(v, entries), entries) + lam * v

            def body(state):
                k, x, r, z, q, rz = state
                hq = hv(q)
                alpha = rz / jnp.vdot(q, hq)
                x = x + alpha * q
                r = r - alpha * hq
                z = r / diagonal
                rz_new = jnp.vdot(r, z)
                return k + 1, x, r, z, z + (rz_new / rz) * q, rz_new

            def go_on(state):
                k, _, r, *_ = state
                return (k < CG_STEPS) & (
                    jnp.linalg.norm(r) > CG_FORCING * jnp.linalg.norm(g))

            z0 = g / diagonal
            steps, x, *_ = jax.lax.while_loop(
                go_on, body, (0, jnp.zeros_like(g), g, z0, z0, jnp.vdot(g, z0)))
            return value, jnp.linalg.norm(g), x, steps

        out = []
        w = jnp.zeros(d, jnp.float32)
        norm_at_zero = None
        for lam in cfg["lambdas"]:
            lam32 = jnp.float32(lam)
            products = 0
            for step in range(1, NEWTON_STEPS + 1):
                value, norm, delta, cg = newton_direction(w, lam32, entries, y)
                value, norm = float(value), float(norm)
                if norm_at_zero is None:
                    norm_at_zero = norm
                if norm <= GRADIENT_STOP * norm_at_zero:
                    break
                products += int(cg)
                scale = 1.0
                while True:
                    trial = w - scale * delta
                    trial_value = float(objective(trial, lam32, entries, y))
                    # within rounding of the value counts as no increase
                    if trial_value <= value + 4 * EPS32 * abs(value) or scale < 1e-3:
                        break
                    scale *= 0.5
                w, value = trial, trial_value
            print(f"reference: lambda {lam:g} Newton steps {step} products {products} "
                  f"float32 gradient norm at the last step's start {norm:.4g} "
                  f"(at zero {norm_at_zero:.4g}) value {value:.6f}", flush=True)
            out.append(np.asarray(w))
        print(f"reference: fit in {time.perf_counter() - started:.1f} s", flush=True)
        return np.stack(out)


def _row_starts(rows: np.ndarray, n: int) -> np.ndarray:
    """Where each row's entries start in the row-sorted triple (every row has
    at least one entry: the generator's contract)."""
    starts = np.searchsorted(rows, np.arange(n))
    if (np.diff(starts) <= 0).any() or starts[-1] >= len(rows):
        raise ValueError("a row without an entry")
    return starts


def evaluate(data: dict, coefficients: np.ndarray, lambdas) -> dict:
    """What the given coefficient vectors ([k, d], one for each of the k
    ``lambdas``) give: {"value" [k]: the objective over every training row,
    "grad_norm" [k]: the norm of its gradient there, "val_margin" [k, n_val]:
    margins of the validation rows}, float64."""
    from scipy.special import expit

    started = time.perf_counter()
    rows, cols = data["rows"], data["cols"]
    vals = data["vals"].astype(np.float64)
    y = data["y"].astype(np.float64)
    starts = _row_starts(rows, len(y))
    rows_v, cols_v = data["rows_val"], data["cols_val"]
    vals_v = data["vals_val"].astype(np.float64)
    starts_v = _row_starts(rows_v, len(data["y_val"]))
    value, grad_norm, val_margin = [], [], []
    for w, lam in zip(np.asarray(coefficients), lambdas):
        w = w.astype(np.float64)
        m = np.add.reduceat(vals * w[cols], starts)
        value.append(np.sum(np.logaddexp(0.0, m) - y * m) + 0.5 * lam * np.vdot(w, w))
        residual = expit(m) - y
        gradient = np.bincount(cols, weights=vals * residual[rows],
                               minlength=len(w)) + lam * w
        grad_norm.append(np.linalg.norm(gradient))
        val_margin.append(np.add.reduceat(vals_v * w[cols_v], starts_v))
        print(f"reference evaluate: lambda {lam:g} value {value[-1]:.6f} float64 "
              f"gradient norm {grad_norm[-1]:.6g} |w| {np.linalg.norm(w):.6g}",
              flush=True)
    print(f"reference evaluate: {len(value)} vectors in "
          f"{time.perf_counter() - started:.1f} s", flush=True)
    return {"value": np.asarray(value), "grad_norm": np.asarray(grad_norm),
            "val_margin": np.stack(val_margin)}
