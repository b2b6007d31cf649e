"""Plain reference for ``game-ml20m-mf``: the full GAME model, fixed effect +
per-user and per-item random effects + a user x item matrix-factorization
term, by block coordinate descent with every block solved EXACTLY (Newton to
the f32 floor), in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``.

The mathematics of ``references/glmix-ml20m.py`` with two more blocks a
sweep, in the program's order: fixed effect, users, items, the
factorization's ROW side, its COLUMN side. With the item factors held fixed,
the objective restricted to one user is a logistic regression of
``latent_factors`` columns over that user's kept rows whose features are the
item factors of those rows and whose offset is every other coordinate's
score; it has one minimizer (L2 on the factors), and Newton finds it. Then
the same for every item against the user factors just solved. That is exact
alternating minimization: the program runs ten L-BFGS iterations a half-step
where this runs Newton to convergence, and how close that comes is part of
what the comparison measures.

No kernel, none of the program's buckets, nothing imported from the program.
The objective is bilinear, so the factors themselves mean nothing (any
rotation of both tables scores alike): what is compared is the SCORE
``p_u . q_i``, and the STARTING FACTORS ARE DATA: ``fit`` is handed the two
tables the program's fit starts from.

``evaluate`` asks a question that does not turn on how far a solver got:
what do GIVEN coefficients, tables and factors (the program's own) score on
the generator's float32 rows? Margins and the mean loss in float64 numpy on
the host, so that the only rounding in the comparison is the program's.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.compare import auc  # the yardstick's exact AUC, not the program's
from benchmark.manifest import HERE, load_module

# the GLMix blocks are the GLMix configuration's reference's, so the same
# constants and the same grouping of an entity's rows (its own, not the
# program's ladder): taken from that file, not written again
_glmix = load_module(os.path.join(HERE, "references", "glmix-ml20m.py"))
NEWTON_STEPS, ROW_BLOCK = _glmix.NEWTON_STEPS, _glmix.ROW_BLOCK
_group_rows = _glmix._group_rows
EVAL_ROWS = 1 << 18  # rows per float64 block of ``evaluate``
SIDES = ("user", "item")


def fit(data: dict, cfg: dict, kept: dict, devices, start: dict) -> dict:
    """data: the generator's host arrays ({"train", "validation"});
    kept: bool [n] masks of the rows the packer kept, {"user", "item"} for
    the random effects and {"mf_user", "mf_item"} for the factorization's
    sides; start: {"mf_user" [users, k], "mf_item" [items, k]} the factors
    the program's fit starts from. Returns per-sweep losses and validation
    AUCs and the final coefficients (numpy): {"losses", "val_auc", "fe",
    "user", "item", "mf_user", "mf_item"}."""
    import jax
    import jax.numpy as jnp

    train, val = data["train"], data["validation"]
    n = len(train["y"])
    l2 = jnp.float32(cfg["l2_weight"])
    l2_mf = jnp.float32(cfg["mf"]["l2_weight"])
    sweeps = int(cfg["coordinate_descent_iterations"])
    alternations = int(cfg["mf"]["alternations"])
    row_block = min(ROW_BLOCK, n)
    pad = (-n) % row_block

    def rows_put(a):
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return jax.device_put(a, devices[0])

    with jax.default_matmul_precision("highest"):
        x_g = rows_put(train["x_global"])
        y = rows_put(train["y"])
        live = rows_put(np.ones(n, np.float32))  # 0 on the padding rows
        x_e = {k: rows_put(train["x_" + k]) for k in SIDES}
        ent = {k: rows_put(train[k].astype(np.int32)) for k in SIDES}

        def blocked(a):
            """[n, ...] -> [blocks, row_block, ...] for a scan over row blocks."""
            return a.reshape((a.shape[0] // row_block, row_block) + a.shape[1:])

        @jax.jit
        def fe_solve(w, x, t, m, offsets):
            xs = (blocked(x), blocked(offsets), blocked(t), blocked(m))
            d = w.shape[0]

            def newton(w, _):
                def block(carry, b):
                    g, h = carry
                    xb, ob, tb, mb = b
                    p = jax.nn.sigmoid(xb @ w + ob)
                    g = g + xb.T @ (mb * (p - tb))
                    h = h + xb.T @ (xb * (mb * p * (1 - p))[:, None])
                    return (g, h), None

                (g, h), _ = jax.lax.scan(
                    block, (jnp.zeros(d, jnp.float32), jnp.zeros((d, d), jnp.float32)), xs)
                g = g + l2 * w
                h = h + l2 * jnp.eye(d, dtype=jnp.float32)
                return w - jnp.linalg.solve(h, g), None

            w, _ = jax.lax.scan(newton, w, None, length=NEWTON_STEPS)
            return w

        def lanes_newton(w, xb, yb, mask, ob, weight):
            """w [e, d] -> every lane's minimizer of its own logistic
            regression on xb [e, cap, d] (mask 0 = padding), offsets ob."""
            eye = jnp.eye(w.shape[1], dtype=jnp.float32)

            def newton(w, _):
                p = jax.nn.sigmoid(jnp.einsum("ecd,ed->ec", xb, w) + ob)
                g = jnp.einsum("ecd,ec->ed", xb, mask * (p - yb)) + weight * w
                h = jnp.einsum("ecd,ec,ecf->edf", xb, mask * p * (1 - p), xb) + weight * eye
                return w - jnp.linalg.solve(h, g[..., None])[..., 0], None

            w, _ = jax.lax.scan(newton, w, None, length=NEWTON_STEPS)
            return w

        @jax.jit
        def gather_group(x, t, index):
            """An entity group's rows, gathered once: x [n, d], t [n],
            index [e, cap] (-1 = padding) -> [e, cap, d], [e, cap], mask."""
            at = jnp.maximum(index, 0)
            return x[at], t[at], (index >= 0).astype(jnp.float32)

        @jax.jit
        def re_solve(w, xb, yb, mask, index, offsets):
            """w [e, d] solved against the other coordinates' scores."""
            return lanes_newton(w, xb, yb, mask, offsets[jnp.maximum(index, 0)], l2)

        @jax.jit
        def mf_solve(w, fixed, fixed_ent, t, index, offsets):
            """One side's factors w [e, k] of a group of entities, the other
            side's table ``fixed`` [E, k] held: the features of a row are the
            fixed side's factors of that row's entity, gathered now."""
            at = jnp.maximum(index, 0)
            return lanes_newton(w, fixed[fixed_ent[at]], t[at],
                                (index >= 0).astype(jnp.float32), offsets[at],
                                l2_mf)

        @jax.jit
        def re_score(table, x, e):
            return jnp.sum(x * table[e], axis=1)

        @jax.jit
        def mf_score(p, q, u, i):
            return jnp.sum(p[u] * q[i], axis=1)

        @jax.jit
        def mean_loss(margin, t, m):
            per_row = jnp.logaddexp(0.0, margin) - t * margin
            return jnp.sum(m * per_row) / jnp.sum(m)

        counts = {k: int(cfg[k + "s"]["count"]) for k in SIDES}
        groups = {
            k: [(jnp.asarray(m), jnp.asarray(i)) + gather_group(x_e[k], y, jnp.asarray(i))
                for m, i in _group_rows(train[k], kept[k], counts[k])]
            for k in SIDES}
        mf_groups = {
            k: [(jnp.asarray(m), jnp.asarray(i))
                for m, i in _group_rows(train[k], kept["mf_" + k], counts[k])]
            for k in SIDES}
        tables = {k: jnp.zeros((counts[k], x_e[k].shape[1]), jnp.float32)
                  for k in SIDES}
        factors = {k: jnp.asarray(start["mf_" + k], jnp.float32) for k in SIDES}
        w_fe = jnp.zeros(x_g.shape[1], jnp.float32)
        scores = {"fe": jnp.zeros_like(y), "user": jnp.zeros_like(y),
                  "item": jnp.zeros_like(y),
                  "mf": mf_score(factors["user"], factors["item"],
                                 ent["user"], ent["item"])}
        v_g = jnp.asarray(val["x_global"])
        v_e = {k: jnp.asarray(val["x_" + k]) for k in SIDES}
        v_ent = {k: jnp.asarray(val[k].astype(np.int32)) for k in SIDES}

        def others(skip):
            return sum(v for name, v in scores.items() if name != skip)

        losses, val_auc = [], []
        for _ in range(sweeps):
            w_fe = fe_solve(w_fe, x_g, y, live, others("fe"))
            scores["fe"] = x_g @ w_fe
            for k in SIDES:
                other = others(k)
                table = tables[k]
                for members, index, xb, yb, mask in groups[k]:
                    table = table.at[members].set(
                        re_solve(table[members], xb, yb, mask, index, other))
                tables[k] = table
                scores[k] = re_score(table, x_e[k], ent[k])
            other = others("mf")
            for _ in range(alternations):
                for k, fixed in (("user", "item"), ("item", "user")):
                    table = factors[k]
                    for members, index in mf_groups[k]:
                        table = table.at[members].set(mf_solve(
                            table[members], factors[fixed], ent[fixed], y,
                            index, other))
                    factors[k] = table
            scores["mf"] = mf_score(factors["user"], factors["item"],
                                    ent["user"], ent["item"])
            losses.append(float(mean_loss(sum(scores.values()), y, live)))
            v_margin = v_g @ w_fe + sum(
                re_score(tables[k], v_e[k], v_ent[k]) for k in SIDES) + mf_score(
                    factors["user"], factors["item"], v_ent["user"], v_ent["item"])
            val_auc.append(auc(np.asarray(v_margin), val["y"]))
        return {"losses": losses, "val_auc": val_auc, "fe": np.asarray(w_fe),
                "user": np.asarray(tables["user"]), "item": np.asarray(tables["item"]),
                "mf_user": np.asarray(factors["user"]),
                "mf_item": np.asarray(factors["item"])}


def mf_scores(split: dict, coefficients: dict, rows=None) -> np.ndarray:
    """float64 ``p_u . q_i`` of the split's rows (all, or those given)."""
    rows = slice(None) if rows is None else rows
    p = np.asarray(coefficients["mf_user"], np.float64)[split["user"][rows]]
    q = np.asarray(coefficients["mf_item"], np.float64)[split["item"][rows]]
    return np.einsum("rk,rk->r", p, q)


def _margins(split: dict, coefficients: dict) -> np.ndarray:
    """[n] float64 margins of the split's float32 rows at the coefficients."""
    w = np.asarray(coefficients["fe"], np.float64)
    tables = {k: np.asarray(coefficients[k], np.float64) for k in SIDES}
    n = len(split["y"])
    out = np.empty(n, np.float64)
    for lo in range(0, n, EVAL_ROWS):
        rows = slice(lo, min(n, lo + EVAL_ROWS))
        m = split["x_global"][rows].astype(np.float64) @ w
        for k in SIDES:
            m += np.einsum("rd,rd->r", split["x_" + k][rows].astype(np.float64),
                           tables[k][split[k][rows]])
        out[rows] = m + mf_scores(split, coefficients, rows)
    return out


def evaluate(data: dict, coefficients: dict) -> dict:
    """What the given coefficients ({"fe" [d], "user" / "item" [e, d],
    "mf_user" / "mf_item" [e, k]}) score: {"loss": mean logistic loss over
    every training row, "val_margin": [n_val] margins of the validation
    rows}, float64."""
    train = data["train"]
    m = _margins(train, coefficients)
    loss = float(np.mean(np.logaddexp(0.0, m) - train["y"].astype(np.float64) * m))
    return {"loss": loss, "val_margin": _margins(data["validation"], coefficients)}
