"""Plain reference for ``game-ymusic-r2``: block coordinate descent over a
fixed effect and three random effects under the SQUARED loss, every block
solved EXACTLY as the ridge problem it is, in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``.

No kernel, no buckets, no Newton rounds, nothing imported from the program.
Per sweep, in the configured order (``global``, ``user``, ``song``,
``artist``): the fixed effect ``(X'X + l2 I) w = X'(y - others)`` over all
rows, ``X'X`` accumulated once in row blocks (it does not change: the loss is
quadratic); then each random effect, for every entity at once,
``(X_e'X_e + l2 I) w_e = X_e'(y - others)`` over the rows the packer KEPT for
that entity (``kept``: the ladder's top rung caps an entity by a stable-id
reservoir, and the cap is part of the semantics compared), the per-entity
Gram matrices summed once by ``segment_sum`` over the entity index (no
grouping by size, no padding), scored on ALL rows. The objective is the
program's: a SUM over rows of ``(m - y)^2 / 2`` plus ``l2 |w|^2 / 2`` a block;
the reported loss is the MEAN of ``(m - y)^2 / 2`` over the rows, the RMSE
the validation rows' ``sqrt(mean (m - y)^2)``, after each sweep.

Departures from the upstream job this mirrors (photon-ml's
``GameTrainingDriverIntegTest`` on Yahoo! Music: ``LINEAR_REGRESSION``,
``RMSE``, ``global`` + ``per-user`` + ``per-song`` + ``per-artist``): no Avro
and no feature bags (the rows are the generator's dense arrays, the entity
ids its indices), generated rows in place of the published ratings, one L2
weight and a fixed number of sweeps in place of the driver's grid.

``evaluate`` asks what does not turn on how far a solver got: what do GIVEN
coefficients (the program's own) score on the generator's float32 rows?
Margins, the mean loss and the RMSE in float64 numpy on the host; and, given
the rows the packer kept, how far the LAST coordinate a sweep updates is from
solving its own ridge systems at that state (every other coordinate's lanes
were moved off their minimum by the coordinates updated after them; the last
one's were not, so its residual is the lanes' own arithmetic and nothing else).
"""

from __future__ import annotations

import numpy as np

ROW_BLOCK = 1 << 18  # rows per block of the Gram sums: bounds the temporaries
#: (coordinate, the block of the rows it is linear in), in the order of update
COORDINATES = (("user", "x_user"), ("song", "x_item"), ("artist", "x_item"))


def _rmse(margin, labels) -> float:
    diff = np.asarray(margin, np.float64) - np.asarray(labels, np.float64)
    return float(np.sqrt(np.mean(diff * diff)))


def fit(data: dict, cfg: dict, kept: dict, devices) -> dict:
    """data: the generator's host arrays ({"train", "validation"}); kept:
    {"user" | "song" | "artist": bool [n]} rows the packer kept. Returns
    per-sweep losses and validation RMSEs and the final coefficients (numpy):
    {"losses", "val_rmse", "fe", "user", "song", "artist"}."""
    import jax
    import jax.numpy as jnp

    train, val = data["train"], data["validation"]
    n = len(train["y"])
    l2 = jnp.float32(cfg["l2_weight"])
    sweeps = int(cfg["coordinate_descent_iterations"])
    counts = {k: int(cfg[k + "s"]["count"]) for k, _ in COORDINATES}
    blocks = [slice(lo, min(n, lo + ROW_BLOCK)) for lo in range(0, n, ROW_BLOCK)]

    with jax.default_device(devices[0]), jax.default_matmul_precision("highest"):
        x_g = jnp.asarray(train["x_global"])
        y = jnp.asarray(train["y"])
        x_e = {name: jnp.asarray(train[name]) for name in {b for _, b in COORDINATES}}
        ent = {k: jnp.asarray(train[k].astype(np.int32)) for k, _ in COORDINATES}
        keep = {k: jnp.asarray(kept[k].astype(np.float32)) for k, _ in COORDINATES}

        @jax.jit
        def gram(x):
            return x.T @ x

        @jax.jit
        def entity_gram(total, x, e, m):
            outer = (x * m[:, None])[:, :, None] * x[:, None, :]
            return total + jax.ops.segment_sum(outer, e, num_segments=total.shape[0])

        d_g = x_g.shape[1]
        gram_g = sum(gram(x_g[rows]) for rows in blocks) + l2 * jnp.eye(d_g, dtype=jnp.float32)
        gram_e = {}
        for k, block in COORDINATES:
            d = x_e[block].shape[1]
            total = jnp.zeros((counts[k], d, d), jnp.float32)
            for rows in blocks:
                total = entity_gram(total, x_e[block][rows], ent[k][rows], keep[k][rows])
            gram_e[k] = total + l2 * jnp.eye(d, dtype=jnp.float32)

        @jax.jit
        def fe_solve(gram, x, target):  # arguments, never constants of the program
            return jnp.linalg.solve(gram, x.T @ target)

        @jax.jit
        def re_solve(gram_k, x, e, m, target):
            rhs = jax.ops.segment_sum(x * (m * target)[:, None], e,
                                      num_segments=gram_k.shape[0])
            return jnp.linalg.solve(gram_k, rhs[..., None])[..., 0]

        @jax.jit
        def re_score(table, x, e):
            return jnp.sum(x * table[e], axis=1)

        tables = {k: jnp.zeros((counts[k], x_e[b].shape[1]), jnp.float32)
                  for k, b in COORDINATES}
        w_fe = jnp.zeros(d_g, jnp.float32)
        scores = {"fe": jnp.zeros_like(y), **{k: jnp.zeros_like(y) for k, _ in COORDINATES}}
        v_g = jnp.asarray(val["x_global"])
        v_e = {name: jnp.asarray(val[name]) for name in x_e}
        v_ent = {k: jnp.asarray(val[k].astype(np.int32)) for k, _ in COORDINATES}

        def others(skip):
            return sum(v for name, v in scores.items() if name != skip)

        losses, val_rmse = [], []
        for _ in range(sweeps):
            w_fe = fe_solve(gram_g, x_g, y - others("fe"))
            scores["fe"] = x_g @ w_fe
            for k, block in COORDINATES:
                tables[k] = re_solve(gram_e[k], x_e[block], ent[k], keep[k],
                                     y - others(k))
                scores[k] = re_score(tables[k], x_e[block], ent[k])
            diff = np.asarray(sum(scores.values()) - y, np.float64)
            losses.append(float(np.mean(0.5 * diff * diff)))
            v_margin = v_g @ w_fe + sum(
                re_score(tables[k], v_e[block], v_ent[k]) for k, block in COORDINATES)
            val_rmse.append(_rmse(v_margin, val["y"]))
        return {"losses": losses, "val_rmse": val_rmse, "fe": np.asarray(w_fe),
                **{k: np.asarray(v) for k, v in tables.items()}}


EVAL_ROWS = 1 << 18  # rows per float64 block of ``evaluate``


def _margins(split: dict, coefficients: dict) -> np.ndarray:
    """[n] float64 margins of the split's float32 rows at the coefficients."""
    w = np.asarray(coefficients["fe"], np.float64)
    tables = {k: np.asarray(coefficients[k], np.float64) for k, _ in COORDINATES}
    n = len(split["y"])
    out = np.empty(n, np.float64)
    for lo in range(0, n, EVAL_ROWS):
        rows = slice(lo, min(n, lo + EVAL_ROWS))
        m = split["x_global"][rows].astype(np.float64) @ w
        for k, block in COORDINATES:
            m += np.einsum("rd,rd->r", split[block][rows].astype(np.float64),
                           tables[k][split[k][rows]])
        out[rows] = m
    return out


def _last_block_residual(train: dict, coefficients: dict, diff: np.ndarray,
                         kept: np.ndarray, l2: float) -> float:
    """``|H w - b|_F / |b|_F`` over the lanes of the last coordinate updated:
    every entity's ridge system ``(X_e'X_e + l2 I) w_e = X_e'(y - others)`` on
    its kept rows, at the given coefficients, in float64 (``diff`` = m - y)."""
    name, block = COORDINATES[-1]
    table = np.asarray(coefficients[name], np.float64)
    rows = np.nonzero(kept)[0]
    e = train[name][rows]
    x = train[block][rows].astype(np.float64)
    own = np.einsum("rd,rd->r", x, table[e])  # the coordinate's own score
    gradient, rhs = l2 * table, np.zeros_like(table)
    for j in range(table.shape[1]):
        gradient[:, j] += np.bincount(e, weights=x[:, j] * diff[rows], minlength=len(table))
        rhs[:, j] = np.bincount(e, weights=x[:, j] * (own - diff[rows]), minlength=len(table))
    return float(np.linalg.norm(gradient) / np.linalg.norm(rhs))


def evaluate(data: dict, coefficients: dict, kept: "dict | None" = None,
             l2_weight: float = 0.0) -> dict:
    """What the given coefficients ({"fe" [d], "user" | "song" | "artist"
    [e, d]}) score: {"loss": mean of (m - y)^2 / 2 over every training row,
    "val_margin": [n_val] margins of the validation rows, "val_rmse"} and, with
    ``kept`` (the packer's masks), "last_block_residual"; float64."""
    train, val = data["train"], data["validation"]
    diff = _margins(train, coefficients) - train["y"].astype(np.float64)
    val_margin = _margins(val, coefficients)
    out = {"loss": float(np.mean(0.5 * diff * diff)), "val_margin": val_margin,
           "val_rmse": _rmse(val_margin, val["y"])}
    if kept is not None:
        out["last_block_residual"] = _last_block_residual(
            train, coefficients, diff, kept[COORDINATES[-1][0]], l2_weight)
    return out
