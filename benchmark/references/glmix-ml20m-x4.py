"""Plain reference for ``glmix-ml20m-x4``: the mathematics of
``references/glmix-ml20m.py`` with the rows held in blocks over the devices
it is given, because the fixed effect's X (20.5 GB) does not fit one chip.

Block coordinate descent with every block solved EXACTLY (Newton to the
f32 floor), in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``. No kernel, no mesh, no sharding
rule, no collective, none of the program's buckets, nothing imported from
the program: device ``k`` holds the ``k``-th contiguous part of the rows as
ordinary single-device arrays, every sum over rows is a sum of the parts'
sums taken on the host, and every entity of a random effect is solved whole
on one device from rows gathered on the host (its own grouping, not the
program's ladder). Per sweep, in the configured order: the fixed effect on
all rows against the other coordinates' scores, then each random effect per
entity on the rows the packer kept (``kept`` — the ladder's top rung is a
cap, and the cap is part of the semantics compared), scored on ALL rows.

``evaluate`` asks a question that does not turn on how far a solver got:
what do GIVEN coefficients (the program's own) score on the generator's
float32 rows? Margins and the mean loss in float64 numpy on the host, in
row blocks on a few threads (numpy drops the GIL; the blocks' results do
not depend on the thread count).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.compare import auc  # the yardstick's exact AUC, not the program's
from benchmark.manifest import HERE, load_module

EVAL_ROWS = 1 << 18  # rows per float64 block of ``evaluate``
EVAL_THREADS = 8

# the same mathematics as the one-chip configuration's reference, so the same
# constants and the same grouping of an entity's rows (its own, not the
# program's ladder): taken from that file, not written again
_one_chip = load_module(os.path.join(HERE, "references", "glmix-ml20m.py"))
NEWTON_STEPS, ROW_BLOCK = _one_chip.NEWTON_STEPS, _one_chip.ROW_BLOCK
_group_rows = _one_chip._group_rows


def fit(data: dict, cfg: dict, kept: dict, devices) -> dict:
    """data: the generator's host arrays ({"train", "validation"});
    kept: {"user": bool [n], "item": bool [n]} rows the packer kept.
    Returns per-sweep losses and validation AUCs and the final coefficients
    (numpy): {"losses", "val_auc", "fe", "user", "item"}."""
    import jax
    import jax.numpy as jnp

    train, val = data["train"], data["validation"]
    devices = list(devices)
    n = len(train["y"])
    l2 = jnp.float32(cfg["l2_weight"])
    sweeps = int(cfg["coordinate_descent_iterations"])
    names = ("user", "item")
    # rows of device k: [bounds[k], bounds[k + 1]), each part padded with
    # dead rows to whole Hessian blocks
    bounds = [n * k // len(devices) for k in range(len(devices) + 1)]
    row_block = min(ROW_BLOCK, max(b - a for a, b in zip(bounds, bounds[1:])))

    def part_put(a: np.ndarray, k: int):
        rows = a[bounds[k]:bounds[k + 1]]
        pad = (-len(rows)) % row_block
        if pad:
            rows = np.concatenate([rows, np.zeros((pad,) + a.shape[1:], a.dtype)])
        return jax.device_put(rows, devices[k])

    def parts_put(a: np.ndarray) -> list:
        return [part_put(a, k) for k in range(len(devices))]

    def on_host(parts: list) -> np.ndarray:
        """[n] vector of the parts, the dead rows cut off."""
        return np.concatenate([np.asarray(p)[: bounds[k + 1] - bounds[k]]
                               for k, p in enumerate(parts)])

    with jax.default_matmul_precision("highest"):
        x_g = parts_put(train["x_global"])
        y = parts_put(train["y"])
        live = parts_put(np.ones(n, np.float32))  # 0 on the dead rows
        x_e = {k: parts_put(train["x_" + k]) for k in names}
        ent = {k: parts_put(train[k].astype(np.int32)) for k in names}

        def blocked(a):
            """[rows, ...] -> [blocks, row_block, ...] for a scan over row blocks."""
            return a.reshape((a.shape[0] // row_block, row_block) + a.shape[1:])

        @jax.jit
        def fe_gradient_hessian(w, x, t, m, offsets):
            """One part's share of the data term's gradient and Hessian."""
            d = w.shape[0]

            def block(carry, b):
                g, h = carry
                xb, ob, tb, mb = b
                p = jax.nn.sigmoid(xb @ w + ob)
                g = g + xb.T @ (mb * (p - tb))
                h = h + xb.T @ (xb * (mb * p * (1 - p))[:, None])
                return (g, h), None

            (g, h), _ = jax.lax.scan(
                block, (jnp.zeros(d, jnp.float32), jnp.zeros((d, d), jnp.float32)),
                (blocked(x), blocked(offsets), blocked(t), blocked(m)))
            return g, h

        @jax.jit
        def newton_step(w, g, h):
            d = w.shape[0]
            return w - jnp.linalg.solve(h + l2 * jnp.eye(d, dtype=jnp.float32),
                                        g + l2 * w)

        def fe_solve(w: np.ndarray, offsets: list) -> np.ndarray:
            for _ in range(NEWTON_STEPS):
                shares = [fe_gradient_hessian(jax.device_put(w, dev), x_g[k], y[k],
                                              live[k], offsets[k])
                          for k, dev in enumerate(devices)]  # all devices at once
                g = sum(jax.device_put(s[0], devices[0]) for s in shares)
                h = sum(jax.device_put(s[1], devices[0]) for s in shares)
                w = np.asarray(newton_step(jax.device_put(w, devices[0]), g, h))
            return w

        @jax.jit
        def re_solve(w, xb, yb, mask, index, offsets):
            """w [e, d] solved against the other coordinates' scores
            (``offsets``: the whole [n] vector, ``index`` into it)."""
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
        def matvec(x, w):
            return x @ w

        @jax.jit
        def loss_sums(margin, t, m):
            per_row = jnp.logaddexp(0.0, margin) - t * margin
            return jnp.sum(m * per_row), jnp.sum(m)

        def entity_groups(k: str) -> list:
            """Every group of entities cut into one piece a device, each
            piece's rows gathered on the host and held on its device:
            (device, members [e], x [e, cap, d], y [e, cap], mask, index)."""
            pieces = []
            for members, index in _group_rows(train[k], kept[k],
                                              int(cfg[k + "s"]["count"])):
                cuts = [len(members) * j // len(devices)
                        for j in range(len(devices) + 1)]
                for dev, lo, hi in zip(devices, cuts, cuts[1:]):
                    if hi == lo:
                        continue
                    at = np.maximum(index[lo:hi], 0)
                    pieces.append((dev, members[lo:hi]) + tuple(
                        jax.device_put(a, dev) for a in (
                            train["x_" + k][at], train["y"][at],
                            (index[lo:hi] >= 0).astype(np.float32), index[lo:hi])))
            return pieces

        groups = {k: entity_groups(k) for k in names}
        tables = {k: np.zeros((int(cfg[k + "s"]["count"]), train["x_" + k].shape[1]),
                              np.float32) for k in names}
        w_fe = np.zeros(train["x_global"].shape[1], np.float32)
        scores = {name: [jnp.zeros_like(p) for p in y] for name in ("fe",) + names}
        v_g = jax.device_put(val["x_global"], devices[0])
        v_e = {k: jax.device_put(val["x_" + k], devices[0]) for k in names}
        v_ent = {k: jax.device_put(val[k].astype(np.int32), devices[0]) for k in names}

        def others(skip: str) -> list:
            """Per part, the sum of every coordinate's scores but ``skip``'s."""
            return [sum(v[k] for name, v in scores.items() if name != skip)
                    for k in range(len(devices))]

        losses, val_auc = [], []
        for _ in range(sweeps):
            w_fe = fe_solve(w_fe, others("fe"))
            scores["fe"] = [matvec(x_g[k], jax.device_put(w_fe, dev))
                            for k, dev in enumerate(devices)]
            for k in names:
                other = on_host(others(k))
                other_on = {dev: jax.device_put(other, dev) for dev in devices}
                solved = [(members, re_solve(jax.device_put(tables[k][members], dev),
                                             xb, yb, mask, index, other_on[dev]))
                          for dev, members, xb, yb, mask, index in groups[k]]
                for members, w in solved:
                    tables[k][members] = np.asarray(w)
                scores[k] = [re_score(jax.device_put(tables[k], dev), x_e[k][j],
                                      ent[k][j]) for j, dev in enumerate(devices)]
            sums = [loss_sums(sum(v[j] for v in scores.values()), y[j], live[j])
                    for j in range(len(devices))]
            losses.append(sum(float(s) for s, _ in sums)
                          / sum(float(m) for _, m in sums))
            v_margin = matvec(v_g, jax.device_put(w_fe, devices[0])) + sum(
                re_score(jax.device_put(tables[k], devices[0]), v_e[k], v_ent[k])
                for k in names)
            val_auc.append(auc(np.asarray(v_margin), val["y"]))
        return {"losses": losses, "val_auc": val_auc, "fe": w_fe,
                "user": tables["user"], "item": tables["item"]}


def _margins(split: dict, coefficients: dict) -> np.ndarray:
    """[n] float64 margins of the split's float32 rows at the coefficients."""
    w = np.asarray(coefficients["fe"], np.float64)
    tables = {k: np.asarray(coefficients[k], np.float64) for k in ("user", "item")}
    n = len(split["y"])
    out = np.empty(n, np.float64)

    def block(lo: int) -> None:
        rows = slice(lo, min(n, lo + EVAL_ROWS))
        m = split["x_global"][rows].astype(np.float64) @ w
        for k in ("user", "item"):
            m += np.einsum("rd,rd->r", split["x_" + k][rows].astype(np.float64),
                           tables[k][split[k][rows]])
        out[rows] = m

    with ThreadPoolExecutor(EVAL_THREADS) as pool:
        list(pool.map(block, range(0, n, EVAL_ROWS)))
    return out


def evaluate(data: dict, coefficients: dict) -> dict:
    """What the given coefficients ({"fe" [d], "user" [e, d], "item" [e, d]})
    score: {"loss": mean logistic loss over every training row,
    "val_margin": [n_val] margins of the validation rows}, float64."""
    train = data["train"]
    m = _margins(train, coefficients)
    loss = float(np.mean(np.logaddexp(0.0, m) - train["y"].astype(np.float64) * m))
    return {"loss": loss, "val_margin": _margins(data["validation"], coefficients)}
