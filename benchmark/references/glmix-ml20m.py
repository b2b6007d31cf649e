"""Plain reference for the GLMix configurations: block coordinate descent
with every block solved EXACTLY (Newton to the f32 floor), in float32
``jax.numpy`` under ``default_matmul_precision("highest")``.

No kernel, none of the program's buckets, nothing imported from the
program. Per sweep, in the configured order: the fixed effect on all rows
against the other coordinates' scores, then each random effect per entity on
the rows the packer kept (``kept`` — the ladder's top rung is a cap, and the
cap is part of the semantics compared), scored on ALL rows. The program runs
a 10-iteration L-BFGS per block where this runs Newton to convergence; how
close 10 iterations come is part of what the comparison measures.

``evaluate`` asks a question that does not turn on how far a solver got:
what do GIVEN coefficients (the program's own) score on the generator's
float32 rows? Margins and the mean loss in float64 numpy on the host, so
that the only rounding in the comparison is the program's.
"""

from __future__ import annotations

import numpy as np

from benchmark.compare import auc  # the yardstick's exact AUC, not the program's

NEWTON_STEPS = 6  # from a warm start or zero, well past the f32 floor
ROW_BLOCK = 1 << 19  # rows per Hessian block: bounds the [block, d] temporary
#: the reference's own padded grouping of an entity's rows (not the
#: program's ladder): capacity classes by kept row count
GROUP_CAPS = (16, 128, 1024)


def _group_rows(entity: np.ndarray, kept: np.ndarray, num_entities: int):
    """[(entity_ids [e], row_index [e, cap] with -1 padding), ...]"""
    rows = np.nonzero(kept)[0]
    order = rows[np.argsort(entity[rows], kind="stable")]
    ent = entity[order]
    counts = np.bincount(ent, minlength=num_entities)
    start = np.concatenate(([0], np.cumsum(counts)[:-1]))
    slot = np.arange(len(order)) - start[ent]
    caps = [c for c in GROUP_CAPS if c < counts.max()] + [int(counts.max())]
    groups, lo = [], 0
    for cap in caps:
        members = np.nonzero((counts > lo) & (counts <= cap))[0]
        lo = cap
        if len(members) == 0:
            continue
        lane = np.full(num_entities, -1, np.int64)
        lane[members] = np.arange(len(members))
        sel = lane[ent] >= 0
        index = np.full((len(members), cap), -1, np.int32)
        index[lane[ent[sel]], slot[sel]] = order[sel]
        groups.append((members.astype(np.int32), index))
    return groups


def fit(data: dict, cfg: dict, kept: dict, devices) -> dict:
    """data: the generator's host arrays ({"train", "validation"});
    kept: {"user": bool [n], "item": bool [n]} rows the packer kept.
    Returns per-sweep losses and validation AUCs and the final coefficients
    (numpy): {"losses", "val_auc", "fe", "user", "item"}."""
    import jax
    import jax.numpy as jnp

    train, val = data["train"], data["validation"]
    n = len(train["y"])
    l2 = jnp.float32(cfg["l2_weight"])
    sweeps = int(cfg["coordinate_descent_iterations"])
    row_block = min(ROW_BLOCK, n)
    pad = (-n) % row_block

    def rows_put(a):
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return jax.device_put(a, devices[0])

    with jax.default_matmul_precision("highest"):
        x_g = rows_put(train["x_global"])
        y = rows_put(train["y"])
        live = rows_put(np.ones(n, np.float32))  # 0 on the padding rows
        x_e = {"user": rows_put(train["x_user"]), "item": rows_put(train["x_item"])}
        ent = {k: rows_put(train[k].astype(np.int32)) for k in ("user", "item")}

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

        @jax.jit
        def gather_group(x, t, index):
            """An entity group's rows, gathered once: x [n, d], t [n],
            index [e, cap] (-1 = padding) -> [e, cap, d], [e, cap], mask."""
            at = jnp.maximum(index, 0)
            return x[at], t[at], (index >= 0).astype(jnp.float32)

        @jax.jit
        def re_solve(w, xb, yb, mask, index, offsets):
            """w [e, d] solved against the other coordinates' scores."""
            ob = offsets[jnp.maximum(index, 0)]
            eye = jnp.eye(w.shape[1], dtype=jnp.float32)

            def newton(w, _):
                p = jax.nn.sigmoid(jnp.einsum("ecd,ed->ec", xb, w) + ob)
                g = jnp.einsum("ecd,ec->ed", xb, mask * (p - yb)) + l2 * w
                h = jnp.einsum("ecd,ec,ecf->edf", xb, mask * p * (1 - p), xb) + l2 * eye
                return w - jnp.linalg.solve(h, g[..., None])[..., 0], None

            w, _ = jax.lax.scan(newton, w, None, length=NEWTON_STEPS)
            return w

        @jax.jit
        def re_score(table, x, e):
            return jnp.sum(x * table[e], axis=1)

        @jax.jit
        def mean_loss(margin, t, m):
            per_row = jnp.logaddexp(0.0, margin) - t * margin
            return jnp.sum(m * per_row) / jnp.sum(m)

        groups = {
            k: [(jnp.asarray(m), jnp.asarray(i)) + gather_group(x_e[k], y, jnp.asarray(i))
                for m, i in _group_rows(train[k], kept[k], int(cfg[k + "s"]["count"]))]
            for k in ("user", "item")}
        tables = {k: jnp.zeros((int(cfg[k + "s"]["count"]), x_e[k].shape[1]),
                               jnp.float32) for k in ("user", "item")}
        w_fe = jnp.zeros(x_g.shape[1], jnp.float32)
        scores = {"fe": jnp.zeros_like(y), "user": jnp.zeros_like(y),
                  "item": jnp.zeros_like(y)}
        v_g = jnp.asarray(val["x_global"])
        v_e = {"user": jnp.asarray(val["x_user"]), "item": jnp.asarray(val["x_item"])}
        v_ent = {k: jnp.asarray(val[k].astype(np.int32)) for k in ("user", "item")}

        losses, val_auc = [], []
        for _ in range(sweeps):
            w_fe = fe_solve(w_fe, x_g, y, live, scores["user"] + scores["item"])
            scores["fe"] = x_g @ w_fe
            for k in ("user", "item"):
                other = sum(v for name, v in scores.items() if name != k)
                table = tables[k]
                for members, index, xb, yb, mask in groups[k]:
                    table = table.at[members].set(
                        re_solve(table[members], xb, yb, mask, index, other))
                tables[k] = table
                scores[k] = re_score(table, x_e[k], ent[k])
            losses.append(float(mean_loss(sum(scores.values()), y, live)))
            v_margin = v_g @ w_fe + sum(
                re_score(tables[k], v_e[k], v_ent[k]) for k in ("user", "item"))
            val_auc.append(auc(np.asarray(v_margin), val["y"]))
        return {"losses": losses, "val_auc": val_auc, "fe": np.asarray(w_fe),
                "user": np.asarray(tables["user"]), "item": np.asarray(tables["item"])}



EVAL_ROWS = 1 << 18  # rows per float64 block of ``evaluate``


def _margins(split: dict, coefficients: dict) -> np.ndarray:
    """[n] float64 margins of the split's float32 rows at the coefficients."""
    w = np.asarray(coefficients["fe"], np.float64)
    tables = {k: np.asarray(coefficients[k], np.float64) for k in ("user", "item")}
    n = len(split["y"])
    out = np.empty(n, np.float64)
    for lo in range(0, n, EVAL_ROWS):
        rows = slice(lo, min(n, lo + EVAL_ROWS))
        m = split["x_global"][rows].astype(np.float64) @ w
        for k in ("user", "item"):
            m += np.einsum("rd,rd->r", split["x_" + k][rows].astype(np.float64),
                           tables[k][split[k][rows]])
        out[rows] = m
    return out


def evaluate(data: dict, coefficients: dict) -> dict:
    """What the given coefficients ({"fe" [d], "user" [e, d], "item" [e, d]})
    score: {"loss": mean logistic loss over every training row,
    "val_margin": [n_val] margins of the validation rows}, float64."""
    train = data["train"]
    m = _margins(train, coefficients)
    loss = float(np.mean(np.logaddexp(0.0, m) - train["y"].astype(np.float64) * m))
    return {"loss": loss, "val_margin": _margins(data["validation"], coefficients)}
