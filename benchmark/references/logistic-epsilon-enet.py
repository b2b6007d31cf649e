"""Plain reference for the dense logistic elastic-net λ grid: for each λ the
minimizer of ``sum_i logloss(x_i.w, y_i) + λ (α |w|_1 + (1 - α)/2 |w|^2)`` by
accelerated proximal gradient (FISTA with the gradient restart of O'Donoghue
and Candes), in float32 ``jax.numpy`` under ``default_matmul_precision
("highest")``.

No kernel, no quasi-Newton history, no orthant, no line search, nothing
imported from the program: a step is ``1 / L`` with ``L`` the Lipschitz
constant of the smooth part's gradient (a quarter of the largest eigenvalue of
``X'X``, by power iteration, plus the L2 weight), followed by the soft
threshold at ``α λ / L``. Every λ is a column of one ``[d, lanes]`` block, so
the lanes share each pass over X (row blocks of 25,000, as the dense
reference's), and every lane starts at zero.

It is stopped by its own optimality residual, the largest violation of the
elastic net's conditions relative to ``α λ``: ``|g_j + (1 - α) λ w_j| <= α λ``
where ``w_j = 0`` and ``g_j + (1 - α) λ w_j + α λ sign(w_j) = 0`` elsewhere,
``g`` the gradient of ``sum logloss``. Between rounds of ``ROUND_STEPS`` steps
the residual is read in float32 on the device; once every lane is within
``RESIDUAL_TARGET`` there, or the lanes outside it have stopped improving
(below), or ``FIT_SECONDS`` have passed, it is computed in FLOAT64 on the host
from the generator's float32 rows and printed for each λ, and the fit goes on
only if a lane is outside the target and still improving.

**What float32 leaves of the residual.** An iterate held in float32 is known
to ``eps |w|``, and the gradient moves by the Hessian times that: along the
few directions the correlated columns share, its eigenvalues are a quarter of
the largest of ``X'X`` (13,930 at 400,000 x 2,000), so the gradient at the
stored iterate is some 1e-3 from the gradient at the point meant, WHATEVER the
number of steps. Against ``α λ`` of 1,200 that is nothing; against the 0.12 of
the grid's smallest λ it is a hundredth. A lane whose residual has reached
that floor is as fitted as the configuration's precision allows: the fit ends
when no lane outside the target has bettered its best residual by a fifth in
``STALL_ROUNDS`` rounds, and says which lanes those are. (Their distance from
the minimizer, the floor over the smallest curvature, is some 1e-4 of a
coefficient: a thousandth of what the comparison's limits are set at.)

``evaluate`` asks what does not turn on how far a solver got: what do GIVEN
coefficient vectors (the program's own, or this file's) give on the
generator's float32 rows? Objective values WITH their L1 term, the
pseudo-gradient's norm, validation margins and non-zero counts, in float64
numpy on the host, so that the only rounding in the comparison is the
program's.
"""

from __future__ import annotations

import time

import numpy as np

ROW_BLOCK = 25_000  # rows per block: bounds the [block, lanes] temporaries
EVAL_ROWS = 1 << 16  # rows per float64 block of ``evaluate``
POWER_STEPS = 30  # power iterations for the largest eigenvalue of X'X
ROUND_STEPS = 50  # proximal-gradient steps between two looks at the residual
MAX_ROUNDS = 40
STALL_ROUNDS = 4  # rounds without a fifth's gain: a lane at float32's floor
#: largest violation of the optimality conditions, relative to α λ, at which a
#: lane counts as fitted
RESIDUAL_TARGET = 1e-3
FIT_SECONDS = 90.0  # the fit's budget on the chip, after the window


def _residual(xp, g, w, l1, l2):
    """[lanes]: the largest violation over the coordinates, relative to l1.
    g, w: [d, lanes]; g the gradient of ``sum logloss`` (no L2 term); ``xp``
    numpy (float64, the host's) or ``jax.numpy`` (float32, between rounds)."""
    s = g + l2 * w
    off_zero = xp.abs(s + l1 * xp.sign(w))
    at_zero = xp.maximum(xp.abs(s) - l1, 0.0)
    return xp.max(xp.where(w != 0.0, off_zero, at_zero), axis=0) / l1


def _host_gradient(data: dict, w: np.ndarray) -> np.ndarray:
    """[d, lanes] float64: the gradient of ``sum logloss`` at each column."""
    from scipy.special import expit

    x, y = data["x"], data["y"].astype(np.float64)
    g = np.zeros_like(w)
    for lo in range(0, len(y), EVAL_ROWS):
        block = x[lo:lo + EVAL_ROWS].astype(np.float64)
        g += block.T @ (expit(block @ w) - y[lo:lo + EVAL_ROWS, None])
    return g


def fit(data: dict, cfg: dict, devices) -> np.ndarray:
    """data: host float32 arrays {"x" [n, d], "y" [n]}. Returns the
    minimizers, [len(lambdas), d] float32, in the order of the
    configuration's ``lambdas``."""
    import jax
    import jax.numpy as jnp

    started = time.perf_counter()
    n, d = data["x"].shape
    alpha = float(cfg["elastic_net_alpha"])
    lambdas = np.asarray(cfg["lambdas"], np.float64)
    l1_64, l2_64 = alpha * lambdas, (1.0 - alpha) * lambdas
    lanes = len(lambdas)
    row_block = min(ROW_BLOCK, n)
    pad = (-n) % row_block

    def rows_put(a):
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return jax.device_put(a, devices[0])

    with jax.default_matmul_precision("highest"):
        x = rows_put(data["x"])
        y = rows_put(data["y"])
        live = rows_put(np.ones(n, np.float32))  # 0 on the padding rows
        l1 = jax.device_put(l1_64.astype(np.float32), devices[0])
        l2 = jax.device_put(l2_64.astype(np.float32), devices[0])

        def blocked(a):
            return a.reshape((a.shape[0] // row_block, row_block) + a.shape[1:])

        # the arrays are ARGUMENTS of the jitted functions: closed over, a
        # 3.2 GB X would be lowered into the program as a constant
        @jax.jit
        def largest_eigenvalue(x):
            def step(v, _):
                def block(acc, xb):
                    return acc + xb.T @ (xb @ v), None

                u, _ = jax.lax.scan(block, jnp.zeros(d, jnp.float32), blocked(x))
                norm = jnp.linalg.norm(u)
                return u / norm, norm

            v0 = jnp.ones(d, jnp.float32) / jnp.sqrt(jnp.float32(d))
            _, norms = jax.lax.scan(step, v0, None, length=POWER_STEPS)
            return norms[-1]

        def gradient(w, x, y, live):
            """[d, lanes]: the gradient of ``sum logloss`` at each column."""
            def block(g, b):
                xb, yb, mb = b
                p = jax.nn.sigmoid(xb @ w)
                return g + xb.T @ (mb[:, None] * (p - yb[:, None])), None

            g, _ = jax.lax.scan(block, jnp.zeros((d, lanes), jnp.float32),
                                (blocked(x), blocked(y), blocked(live)))
            return g

        @jax.jit
        def round_of_steps(w, v, t, step, x, y, live, l1, l2):
            """ROUND_STEPS accelerated proximal steps of every lane; then the
            lanes' residuals at ``w``, float32."""
            def one(carry, _):
                w, v, t = carry
                u = v - step * (gradient(v, x, y, live) + l2 * v)
                w_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
                # restart a lane whose momentum points uphill
                uphill = jnp.sum((v - w_new) * (w_new - w), axis=0) > 0.0
                t_new = jnp.where(uphill, 1.0, 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)))
                beta = jnp.where(uphill, 0.0, (t - 1.0) / t_new)
                return (w_new, w_new + beta * (w_new - w), t_new), None

            (w, v, t), _ = jax.lax.scan(one, (w, v, t), None, length=ROUND_STEPS)
            return w, v, t, _residual(jnp, gradient(w, x, y, live), w, l1, l2)

        top = float(largest_eigenvalue(x))
        # 2 % over the power iteration's estimate, which approaches from below
        step = 1.0 / (1.02 * 0.25 * top + l2)
        w = v = jnp.zeros((d, lanes), jnp.float32)
        t = jnp.ones(lanes, jnp.float32)
        rounds = 0
        best = [np.full(lanes, np.inf)]  # each lane's best residual, round by round
        while True:
            rounds += 1
            w, v, t, residual32 = round_of_steps(w, v, t, step, x, y, live, l1, l2)
            residual32 = np.asarray(residual32)
            best.append(np.minimum(best[-1], residual32))
            outside = residual32 > 0.5 * RESIDUAL_TARGET
            stalled = rounds > STALL_ROUNDS and not np.any(
                outside & (best[-1] < 0.8 * best[-1 - STALL_ROUNDS]))
            spent = time.perf_counter() - started
            if not (rounds == MAX_ROUNDS or spent > FIT_SECONDS or stalled
                    or not outside.any()):
                continue
            w_host = np.asarray(w)
            w64 = w_host.astype(np.float64)
            residual = _residual(np, _host_gradient(data, w64), w64, l1_64, l2_64)
            spent = time.perf_counter() - started
            if (residual.max() <= RESIDUAL_TARGET or rounds == MAX_ROUNDS
                    or spent > FIT_SECONDS or stalled):
                break
    print(f"reference: largest eigenvalue of X'X {top:.6g}, {rounds * ROUND_STEPS} "
          f"proximal steps in {spent:.1f} s; float64 residual by lambda: "
          + " ".join(f"{lam:.6g}:{r:.2e}" for lam, r in zip(lambdas, residual)),
          flush=True)
    outside = lambdas[residual > RESIDUAL_TARGET]
    if len(outside):
        print(f"reference: {len(outside)} lanes outside the residual target "
              f"{RESIDUAL_TARGET:g}, "
              + ("at float32's floor" if stalled else "OUT OF TIME OR ROUNDS")
              + f": largest {residual.max():.2e}, lambdas {outside.tolist()}", flush=True)
    return w_host.T.copy()


def evaluate(data: dict, coefficients: np.ndarray, lambdas, alpha: float) -> dict:
    """What the given coefficient vectors ([k, d], one for each of the k
    ``lambdas``) give: {"value" [k]: the elastic-net objective over every
    training row, its L1 term included, "grad_norm" [k]: the norm of its
    pseudo-gradient there (the minimum-norm subgradient: what OWL-QN
    reports), "val_margin" [k, n_val]: margins of the validation rows,
    "nonzeros" [k]}, float64."""
    from scipy.special import expit

    w = np.asarray(coefficients, np.float64).T  # [d, k]
    lam = np.asarray(lambdas, np.float64)
    l1, l2 = alpha * lam, (1.0 - alpha) * lam
    x, y = data["x"], data["y"].astype(np.float64)
    value = 0.5 * l2 * np.sum(w * w, axis=0) + l1 * np.sum(np.abs(w), axis=0)
    gradient = l2 * w
    for lo in range(0, len(y), EVAL_ROWS):
        rows = slice(lo, lo + EVAL_ROWS)
        block = x[rows].astype(np.float64)
        m = block @ w
        value = value + np.sum(np.logaddexp(0.0, m) - y[rows, None] * m, axis=0)
        gradient = gradient + block.T @ (expit(m) - y[rows, None])
    right, left = gradient + l1, gradient - l1
    pseudo = np.where(w > 0.0, right, np.where(
        w < 0.0, left, np.where(right < 0.0, right, np.where(left > 0.0, left, 0.0))))
    val = np.concatenate([
        data["x_val"][lo:lo + EVAL_ROWS].astype(np.float64) @ w
        for lo in range(0, len(data["y_val"]), EVAL_ROWS)])
    return {"value": value, "grad_norm": np.linalg.norm(pseudo, axis=0),
            "val_margin": val.T, "nonzeros": np.count_nonzero(w, axis=0)}
