"""Generator of the full-GAME data set: ``datagen.make_glmix``'s arrays, with a
seeded low-rank user x item term ADDED to the margin before the labels are
drawn.

Features, entity ids, the rows' order and the validation split are
``make_glmix``'s own, bit for bit (it is called, not copied): the fixed
effect and both random effects of ``game-ml20m-mf`` see the X that
``glmix-ml20m`` sees. Only ``y`` differs. To draw it the GLMix margin is
computed anew from the dense rows and the true coefficients (``make_glmix``
returns neither; they come from the configuration's ``data_seed`` stream 0,
read here in its order), the interaction ``p_u . q_i`` of seeded true
factors of ``mf.true_rank`` columns and deviation ``mf.true_scale`` is
added, and a label is drawn for each CANONICAL row from streams of their own
(``data_seed``, 12 + the split's stream, chunk).

Every seed poses the same fit, as in the other cells (``datagen.py``): the
truth is indexed by an entity's size rank and the labels by canonical row,
so ``--seed`` still only names the entities and orders the validation rows.

The STARTING factors of the fit are data too, drawn here and not by the
program: ``init_factors``' rule (normal, deviation 1 / sqrt(rank)) in numpy
from a stream of ``data_seed``, by size rank, handed over laid out by id
(``start_factors``). Program and reference are both given these arrays, so a
program that drew its own badly (zeros are a stationary point of the
bilinear objective) cannot agree with a reference that started elsewhere.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import datagen

#: streams of the configuration's ``data_seed`` this file draws from, clear
#: of ``make_glmix``'s 0 to 3
TRUE_FACTORS_STREAM = 4
START_FACTORS_STREAM = 5
LABEL_STREAM_BASE = 12


def _glmix_truth(cfg: dict):
    """``make_glmix``'s true coefficients, by size rank: the same stream read
    in the same order. ([d_g + 1], [users, d_e + 1], [items, d_e + 1]), the
    intercepts in the last column, as the rows carry them."""
    w = cfg["widths"]
    d_g, d_e = int(w["global_features"]), int(w["entity_features"])
    users, items = int(cfg["users"]["count"]), int(cfg["items"]["count"])
    truth = np.random.default_rng([int(cfg["data_seed"]), 0])
    w_g = truth.normal(scale=0.25, size=d_g).astype(np.float32)
    w_u = truth.normal(scale=0.3, size=(users, d_e)).astype(np.float32)
    b_u = truth.normal(scale=0.5, size=users).astype(np.float32)
    w_i = truth.normal(scale=0.3, size=(items, d_e)).astype(np.float32)
    b_i = truth.normal(scale=0.5, size=items).astype(np.float32)
    return (np.append(w_g, np.float32(-0.3)),
            np.column_stack([w_u, b_u]), np.column_stack([w_i, b_i]))


def true_factors(cfg: dict):
    """([users, rank], [items, rank]) float32 true factors by size rank."""
    mf = cfg["mf"]
    rng = np.random.default_rng([int(cfg["data_seed"]), TRUE_FACTORS_STREAM])
    p, q = (rng.normal(scale=float(mf["true_scale"]),
                       size=(int(cfg[side]["count"]), int(mf["true_rank"])))
            for side in ("users", "items"))
    return p.astype(np.float32), q.astype(np.float32)


def start_factors(cfg: dict):
    """([users, k], [items, k]) float32 starting factors by size rank:
    normal, deviation 1 / sqrt(k), k = ``mf.latent_factors``."""
    k = int(cfg["mf"]["latent_factors"])
    rng = np.random.default_rng([int(cfg["data_seed"]), START_FACTORS_STREAM])
    return tuple(rng.normal(scale=k ** -0.5, size=(int(cfg[side]["count"]), k))
                 .astype(np.float32) for side in ("users", "items"))


def make_game(cfg: dict, seed: int) -> dict:
    """``make_glmix``'s dictionary with ``y`` of both splits drawn anew from
    the GLMix margin plus the interaction, ``user_rank`` / ``item_rank``
    (int32, id -> size rank) and ``start_factors`` ({"mf_user", "mf_item"},
    by id) beside it."""
    data = datagen.make_glmix(cfg, seed)
    n_val = int(cfg["validation_rows"])
    data_seed = int(cfg["data_seed"])
    # the layout stream as make_glmix reads it: ids by rank, then where each
    # canonical validation row sits
    layout = np.random.default_rng([seed, 0])
    user_id = layout.permutation(int(cfg["users"]["count"]))
    item_id = layout.permutation(int(cfg["items"]["count"]))
    val_position = layout.permutation(n_val)
    rank = {"user": np.argsort(user_id).astype(np.int32),
            "item": np.argsort(item_id).astype(np.int32)}
    w_g, w_u, w_i = _glmix_truth(cfg)
    p, q = true_factors(cfg)

    def relabel(split: dict, stream: int, position: "np.ndarray | None") -> None:
        rows = len(split["y"])
        chunks = range((rows + datagen.CHUNK_ROWS - 1) // datagen.CHUNK_ROWS)
        # one uniform a CANONICAL row (a chunk's draws depend on data_seed,
        # stream and chunk only), taken to where the seed put the row: the
        # label of a rating does not turn on its place
        uniform = np.concatenate([
            np.random.default_rng([data_seed, LABEL_STREAM_BASE + stream, c]).random(
                min(rows, (c + 1) * datagen.CHUNK_ROWS) - c * datagen.CHUNK_ROWS)
            for c in chunks])
        if position is not None:
            uniform = uniform[np.argsort(position)]
        y = np.empty(rows, np.float32)

        def fill(chunk: int) -> None:
            at = slice(chunk * datagen.CHUNK_ROWS,
                       min(rows, (chunk + 1) * datagen.CHUNK_ROWS))
            u, i = rank["user"][split["user"][at]], rank["item"][split["item"][at]]
            margin = split["x_global"][at] @ w_g
            margin += np.einsum("rd,rd->r", split["x_user"][at], w_u[u])
            margin += np.einsum("rd,rd->r", split["x_item"][at], w_i[i])
            margin += np.einsum("rk,rk->r", p[u], q[i])
            y[at] = uniform[at] < 1.0 / (1.0 + np.exp(-margin.astype(np.float64)))

        with ThreadPoolExecutor(datagen.GEN_THREADS) as pool:
            list(pool.map(fill, chunks))
        split["y"] = y

    relabel(data["train"], 2, None)
    relabel(data["validation"], 3, val_position)
    data["user_rank"], data["item_rank"] = rank["user"], rank["item"]
    start_u, start_i = start_factors(cfg)
    data["start_factors"] = {"mf_user": start_u[rank["user"]],
                             "mf_item": start_i[rank["item"]]}
    return data
