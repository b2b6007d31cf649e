"""Generators: the configuration fixes the STRUCTURE, the seed fixes the VALUES.

Everything that decides how much work a run is — row count, entity counts,
the size of every entity, non-zeros per row, therefore every bucket block
shape and every compiled program — is computed from the configuration file
and is identical for every seed. The VALUES of the data set (features, true
coefficients, labels, the order of the training rows) come from the
configuration's ``data_seed``; the seed decides which entity id carries
which size and in which order the validation rows lie — no more, because
float32 line searches turn on rounding (see ``make_glmix``). The program
receives arrays, never a seed.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows generated per task; a chunk's draws depend on (seed, stream, chunk)
#: only, so the arrays are the same whatever the thread count
CHUNK_ROWS = 1 << 19
GEN_THREADS = 8


def size_profile(count: int, total: int, a: float, lo: int, hi: int) -> np.ndarray:
    """Entity sizes by rank, computed and not sampled:
    ``size(rank) = clip(round(c / rank**a), lo, hi)`` with ``c`` solved so
    that the sizes sum to ``total`` exactly — the remainder is handed out
    one row each from rank 1 down."""
    if not count * lo <= total <= count * hi:
        raise ValueError(
            f"{count} entities of {lo}..{hi} rows cannot hold {total} rows")
    decay = np.arange(1, count + 1, dtype=np.float64) ** -float(a)

    def sizes(c: float) -> np.ndarray:
        return np.clip(np.round(c * decay), lo, hi).astype(np.int64)

    c_lo, c_hi = 0.0, float(hi) / decay[-1]
    for _ in range(200):  # largest c whose sizes do not pass the total
        mid = 0.5 * (c_lo + c_hi)
        if sizes(mid).sum() <= total:
            c_lo = mid
        else:
            c_hi = mid
    out = sizes(c_lo)
    rest = int(total - out.sum())
    while rest > 0:
        room = np.nonzero(out < hi)[0][:rest]
        out[room] += 1
        rest -= len(room)
    return out


def entity_sizes(entity_cfg: dict, rows: int) -> np.ndarray:
    return size_profile(int(entity_cfg["count"]), rows, float(entity_cfg["a"]),
                        int(entity_cfg["min"]), int(entity_cfg["max"]))


def _distinct_columns(rng, n: int, d: int, k: int) -> np.ndarray:
    """[n, k] column ids, distinct within each row: a window of a random
    permutation of range(d) at a per-row random offset."""
    perm = rng.permutation(d)
    start = rng.integers(0, d, size=n)
    return perm[(start[:, None] + np.arange(k)[None, :]) % d]


def _ranks_of_rows(rng, sizes: np.ndarray) -> np.ndarray:
    """[sum(sizes)] size-rank of the entity each canonical row belongs to."""
    per_row = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    return per_row[rng.permutation(len(per_row))]


def make_glmix(cfg: dict, seed: int) -> dict:
    """Host arrays of one GLMix data set at the configuration's shape.

    The data set itself — feature columns and values, true coefficients,
    labels, the order of the training rows, which rows belong to the entity
    of which size rank — is drawn from the configuration's ``data_seed``:
    MovieLens-20M is ONE data set. ``seed`` decides which entity id carries
    which rank (so where every entity sits in its bucket and in the
    coefficient tables) and in which order the validation rows lie. That is
    as far as a seed can go without changing the work: the float32 line
    searches turn on rounding. With the label noise and the order of the
    training rows drawn from the seed as well (tried on the chip, PR 23), 4
    of 11 seeds ended a fixed-effect solve in a failed line search of 25
    trials, and the rate spread by 9 to 10 % across seeds at either live
    stop tried (PERF.md 6): no bound the contract allows admits that. Every
    seed poses the same fit, entity for entity bit for bit.

    Returns ``{"train": split, "validation": split, "user_sizes",
    "item_sizes"}``; a split holds dense ``x_global [n, d_g+1]``,
    ``x_user``, ``x_item`` ``[n, d_e+1]`` (last column the intercept),
    ``y``, ``user``, ``item``.
    """
    w = cfg["widths"]
    d_g, d_e = int(w["global_features"]), int(w["entity_features"])
    k_g, k_e = int(w["global_nnz"]), int(w["entity_nnz"])
    n, n_val = int(cfg["rows"]), int(cfg["validation_rows"])
    data_seed = int(cfg["data_seed"])
    user_sizes = entity_sizes(cfg["users"], n)
    item_sizes = entity_sizes(cfg["items"], n)

    truth = np.random.default_rng([data_seed, 0])
    w_g = truth.normal(scale=0.25, size=d_g).astype(np.float32)
    w_u = truth.normal(scale=0.3, size=(len(user_sizes), d_e)).astype(np.float32)
    b_u = truth.normal(scale=0.5, size=len(user_sizes)).astype(np.float32)
    w_i = truth.normal(scale=0.3, size=(len(item_sizes), d_e)).astype(np.float32)
    b_i = truth.normal(scale=0.5, size=len(item_sizes)).astype(np.float32)
    b_g = np.float32(-0.3)

    ranks = np.random.default_rng([data_seed, 1])
    user_rank = _ranks_of_rows(ranks, user_sizes)
    item_rank = _ranks_of_rows(ranks, item_sizes)
    # validation rows rate the (user, item) pairs of evenly spaced train rows
    pick = (np.arange(n_val, dtype=np.int64) * n) // max(n_val, 1)

    layout = np.random.default_rng([seed, 0])
    user_id = layout.permutation(len(user_sizes)).astype(np.int32)
    item_id = layout.permutation(len(item_sizes)).astype(np.int32)

    def split(stream: int, rows: int, u_rank: np.ndarray, i_rank: np.ndarray,
              reorder: bool) -> dict:
        # canonical row -> where it sits
        position = layout.permutation(rows) if reorder else np.arange(rows)
        out = {
            "x_global": np.zeros((rows, d_g + 1), np.float32),
            "x_user": np.zeros((rows, d_e + 1), np.float32),
            "x_item": np.zeros((rows, d_e + 1), np.float32),
            "y": np.zeros(rows, np.float32),
            "user": np.zeros(rows, np.int32), "item": np.zeros(rows, np.int32),
        }

        def fill(chunk: int) -> None:
            lo, hi = chunk * CHUNK_ROWS, min(rows, (chunk + 1) * CHUNK_ROWS)
            m = hi - lo
            rng = np.random.default_rng([data_seed, stream, chunk])
            at = position[lo:hi]
            u, i = u_rank[lo:hi], i_rank[lo:hi]
            margin = np.full(m, b_g, np.float32) + b_u[u] + b_i[i]
            for name, d, k, coef in (("x_global", d_g, k_g, None),
                                     ("x_user", d_e, k_e, w_u[u]),
                                     ("x_item", d_e, k_e, w_i[i])):
                cols = _distinct_columns(rng, m, d, k)
                vals = rng.standard_normal((m, k), dtype=np.float32)
                out[name][at[:, None], cols] = vals
                out[name][at, d] = 1.0
                picked = w_g[cols] if coef is None else np.take_along_axis(
                    coef, cols, axis=1)
                margin += (vals * picked).sum(1)
            p = 1.0 / (1.0 + np.exp(-margin.astype(np.float64)))
            out["y"][at] = rng.random(m) < p
            out["user"][at] = user_id[u]
            out["item"][at] = item_id[i]

        chunks = range((rows + CHUNK_ROWS - 1) // CHUNK_ROWS)
        with ThreadPoolExecutor(GEN_THREADS) as pool:
            list(pool.map(fill, chunks))
        return out

    return {
        "train": split(2, n, user_rank, item_rank, reorder=False),
        "validation": split(3, n_val, user_rank[pick], item_rank[pick], reorder=True),
        "user_sizes": user_sizes, "item_sizes": item_sizes,
    }
