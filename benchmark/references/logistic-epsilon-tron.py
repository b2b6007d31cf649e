"""Plain reference for the dense logistic λ-path: for each λ the EXACT
minimizer of ``sum_i logloss(x_i.w, y_i) + λ/2 |w|^2`` by full Newton steps,
in float32 ``jax.numpy`` under ``default_matmul_precision("highest")``.

No kernel, no line search, no history, nothing imported from the program.
The Hessian ``X' D X + λ I`` (d = 2,000: 16 MB) is summed over row blocks; a
step is halved while it does not decrease the objective (from zero at the
smallest λ the first full step can overshoot), and the iteration ends once a
step no longer moves the coefficients in float32. The program runs at most
15 rounds of a truncated-CG trust-region Newton for each λ where this runs
full Newton steps to convergence; how close those come is part of what the
comparison measures.

``evaluate`` asks what does not turn on how far a solver got: what do GIVEN
coefficient vectors (the program's own, or this file's) give on the
generator's float32 rows? Objective values and validation margins in
float64 numpy on the host, so that the only rounding in the comparison is
the program's.

This file is the configuration ``logistic-epsilon-tron``'s copy of
``logistic-epsilon.py`` (the same rows, the same objective, the same exact
minimizer: a reference does not turn on the program's solver), plus what
that configuration's own reading needs: ``hessian_vector``, the product
``(X' D X + λ I) v`` at given coefficients in float64 numpy, against which
the products the program's trust-region Newton solve is made of are held.
"""

from __future__ import annotations

import numpy as np

MAX_NEWTON_STEPS = 25
ROW_BLOCK = 25_000  # rows per Hessian block: bounds the [block, d] temporaries
EVAL_ROWS = 1 << 16  # rows per float64 block of ``evaluate``
#: a Newton step smaller than this against the coefficients' norm is float32
#: rounding: the minimizer is reached
STEP_FLOOR = 2e-6
EPS32 = float(np.finfo(np.float32).eps)


def fit(data: dict, cfg: dict, devices) -> np.ndarray:
    """data: host float32 arrays {"x" [n, d], "y" [n]}. Returns the
    minimizers, [len(lambdas), d] float32, in the order of the
    configuration's ``lambdas`` (each λ warm-started from the one before:
    the minimizer does not depend on where Newton starts)."""
    import jax
    import jax.numpy as jnp

    n, d = data["x"].shape
    row_block = min(ROW_BLOCK, n)
    pad = (-n) % row_block

    def rows_put(a):
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return jax.device_put(a, devices[0])

    with jax.default_matmul_precision("highest"):
        x = rows_put(data["x"])
        y = rows_put(data["y"])
        live = rows_put(np.ones(n, np.float32))  # 0 on the padding rows

        def blocked(a):
            return a.reshape((a.shape[0] // row_block, row_block) + a.shape[1:])

        # the arrays are ARGUMENTS of the jitted functions: closed over, a
        # 3.2 GB X would be lowered into the program as a constant
        @jax.jit
        def objective(w, lam, x, y, live):
            def block(total, b):
                xb, yb, mb = b
                m = xb @ w
                return total + jnp.sum(mb * (jnp.logaddexp(0.0, m) - yb * m)), None

            total, _ = jax.lax.scan(block, jnp.float32(0.0),
                                    (blocked(x), blocked(y), blocked(live)))
            return total + 0.5 * lam * jnp.vdot(w, w)

        @jax.jit
        def newton_step(w, lam, x, y, live):
            def block(carry, b):
                g, h = carry
                xb, yb, mb = b
                p = jax.nn.sigmoid(xb @ w)
                g = g + xb.T @ (mb * (p - yb))
                h = h + xb.T @ (xb * (mb * p * (1 - p))[:, None])
                return (g, h), None

            (g, h), _ = jax.lax.scan(
                block, (jnp.zeros(d, jnp.float32), jnp.zeros((d, d), jnp.float32)),
                (blocked(x), blocked(y), blocked(live)))
            g = g + lam * w
            h = h + lam * jnp.eye(d, dtype=jnp.float32)
            return jnp.linalg.solve(h, g)

        out = []
        w = jnp.zeros(d, jnp.float32)
        for lam in cfg["lambdas"]:
            lam32 = jnp.float32(lam)
            value = float(objective(w, lam32, x, y, live))
            for step in range(1, MAX_NEWTON_STEPS + 1):
                delta = newton_step(w, lam32, x, y, live)
                moved = float(jnp.linalg.norm(delta)
                              / jnp.maximum(jnp.linalg.norm(w), 1e-30))
                if moved <= STEP_FLOOR:
                    break
                scale = 1.0
                while True:
                    trial = w - scale * delta
                    trial_value = float(objective(trial, lam32, x, y, live))
                    # within rounding of the value counts as no increase
                    if trial_value <= value + 4 * EPS32 * abs(value) or scale < 1e-3:
                        break
                    scale *= 0.5
                w, value = trial, trial_value
            else:
                raise RuntimeError(f"Newton did not converge at lambda {lam}")
            print(f"reference: lambda {lam:g} Newton steps {step} "
                  f"last move {moved:.2e} value {value:.6f}", flush=True)
            out.append(np.asarray(w))
        return np.stack(out)


def evaluate(data: dict, coefficients: np.ndarray, lambdas) -> dict:
    """What the given coefficient vectors ([k, d], one for each of the k
    ``lambdas``) give: {"value" [k]: the objective over every training row,
    "grad_norm" [k]: the norm of its gradient there, "val_margin" [k, n_val]:
    margins of the validation rows}, float64."""
    from scipy.special import expit

    w = np.asarray(coefficients, np.float64).T  # [d, k]
    lam = np.asarray(lambdas, np.float64)
    x, y = data["x"], data["y"].astype(np.float64)
    value = 0.5 * lam * np.sum(w * w, axis=0)
    gradient = lam * w
    for lo in range(0, len(y), EVAL_ROWS):
        rows = slice(lo, lo + EVAL_ROWS)
        block = x[rows].astype(np.float64)
        m = block @ w
        value = value + np.sum(np.logaddexp(0.0, m) - y[rows, None] * m, axis=0)
        gradient = gradient + block.T @ (expit(m) - y[rows, None])
    val = np.concatenate([
        data["x_val"][lo:lo + EVAL_ROWS].astype(np.float64) @ w
        for lo in range(0, len(data["y_val"]), EVAL_ROWS)])
    return {"value": value, "grad_norm": np.linalg.norm(gradient, axis=0),
            "val_margin": val.T}


def hessian_vector(data: dict, coefficients: np.ndarray, vectors: np.ndarray,
                   lambdas) -> np.ndarray:
    """``(X' D_k X + λ_k I) v_k`` for each of the k ``lambdas``, [k, d]
    float64: ``D_k`` the logistic loss's second derivative ``p (1 - p)`` at
    the margins of ``coefficients[k]`` on the training rows, ``v_k`` =
    ``vectors[k]``. Two float64 products a row block, nothing kept."""
    from scipy.special import expit

    w = np.asarray(coefficients, np.float64).T  # [d, k]
    v = np.asarray(vectors, np.float64).T
    x = data["x"]
    out = np.asarray(lambdas, np.float64) * v
    for lo in range(0, x.shape[0], EVAL_ROWS):
        block = x[lo:lo + EVAL_ROWS].astype(np.float64)
        p = expit(block @ w)
        out = out + block.T @ (p * (1.0 - p) * (block @ v))
    return out.T
