"""Generator of a sparse two-class data set at a configuration's shape: the
flat COO triple (row, column, value) and the labels, as host numpy arrays.

The configuration fixes the shape and, through its ``data_seed``, every value
the FIT sees: the training entries, the rows' order, the labels. ``seed`` (the
run's ``--seed``) decides the order of the VALIDATION rows and nothing else,
for the reason ``datagen_dense`` gives: float32 line searches turn on
rounding, so a seed that reached the training rows would change the amount of
work, not only the values. The program receives arrays, never a seed.

What is drawn (``assumed`` in the configuration file says why), every law's
numbers under the configuration's ``generator`` key:

- a row's non-zeros: ``clip(round(lognormal(log(median), sigma)), min, max)``
  draws of a column, duplicates within a row dropped (features are binary);
- a draw's column: rank ``r`` in ``[0, d)`` from the bounded power law
  ``p(r) ~ (r + 1) ** -exponent`` (inverse CDF of the continuous law on
  ``[1, d + 1)``, floored), then scattered over the id space by the
  multiplicative bijection ``column = (r * multiplier + offset) mod d``
  (``multiplier`` coprime to ``d``), so that the hottest columns are no
  contiguous block;
- values: 1 on every kept entry, then every row scaled to unit length;
- labels: Bernoulli(sigmoid(margin)) of a seeded sparse true model (a share
  ``true_density`` of the columns carries a standard normal weight), margins
  scaled to the deviation ``true_margin_std`` over the training rows.

No array of the feature dimension is counted into (no ``bincount`` over d):
entries are sorted by one int64 key ``row * d + column``, which also leaves
the triple row-major sorted and unique, as ``SparseLabeledPointBatch.from_coo``
wants it.
"""

from __future__ import annotations

import math

import numpy as np


def multiplier_for(d: int) -> int:
    """The bijection's multiplier: the smallest integer at or above
    ``0.618 d`` that is coprime to ``d`` (a stride of the golden section
    spreads consecutive ranks evenly over the ids)."""
    a = max(1, int(0.6180339887498949 * d))
    while math.gcd(a, d) != 1:
        a += 1
    return a


def column_of_rank(rank: np.ndarray, d: int, offset: int) -> np.ndarray:
    """``(rank * multiplier + offset) mod d``, int64 (the product stays under
    2**63 for any d an int32 index reaches)."""
    out = rank.astype(np.int64)
    out *= multiplier_for(d)
    out += int(offset)
    out %= d
    return out


def _draw_entries(rng, n: int, d: int, law: dict):
    """Row-major sorted, unique (rows int32, cols int32, counts int64 [n])."""
    per_row = law["nonzeros_per_row"]
    drawn = np.rint(rng.lognormal(math.log(per_row["median_drawn"]),
                                  per_row["sigma"], n))
    np.clip(drawn, per_row["min"], per_row["max"], out=drawn)
    drawn = drawn.astype(np.int64)
    total = int(drawn.sum())
    # inverse CDF of p(x) ~ x ** -a on [1, d + 1): x = (1 - u (1 - hi)) ** (1 / (1 - a))
    a = float(law["columns"]["exponent"])
    u = rng.random(total)
    hi = (d + 1.0) ** (1.0 - a)
    u *= hi - 1.0
    u += 1.0
    np.power(u, 1.0 / (1.0 - a), out=u)
    u -= 1.0  # rank from 0
    np.clip(u, 0, d - 1, out=u)
    key = column_of_rank(u.astype(np.int64), d, law["columns"]["offset"])
    del u
    key += np.repeat(np.arange(n, dtype=np.int64) * d, drawn)
    key.sort()
    keep = np.empty(total, bool)
    keep[0] = True
    np.not_equal(key[1:], key[:-1], out=keep[1:])
    key = key[keep]
    rows = key // d
    key -= rows * d
    rows = rows.astype(np.int32)
    counts = np.bincount(rows, minlength=n)  # over the ROWS, never over d
    return rows, key.astype(np.int32), counts


def _split(rng, n: int, d: int, law: dict, w_true: np.ndarray):
    """One split's (rows, cols, vals, raw margins [n]) before the labels."""
    rows, cols, counts = _draw_entries(rng, n, d, law)
    if counts.min() < 1:
        raise ValueError("a row drew no entry")
    scale = (1.0 / np.sqrt(counts)).astype(np.float32)
    vals = scale[rows]
    starts = np.zeros(n, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    margin = np.add.reduceat(w_true[cols].astype(np.float64), starts) * scale
    return rows, cols, vals, margin


def make_sparse(cfg: dict, seed: int) -> dict:
    """{"rows", "cols", "vals", "y"} of the training rows and the same four
    with ``_val`` of the validation rows: int32 indices, float32 values and
    labels, each triple sorted by (row, column) with no pair twice."""
    n, n_val, d = int(cfg["rows"]), int(cfg["validation_rows"]), int(cfg["features"])
    law = cfg["generator"]
    data_seed = int(cfg["data_seed"])
    rng = np.random.default_rng([data_seed, 0])
    w_true = rng.standard_normal(d, np.float32)
    w_true *= rng.random(d, np.float32) < float(law["true_density"])

    rows, cols, vals, margin = _split(
        np.random.default_rng([data_seed, 1]), n, d, law, w_true)
    slope = float(law["true_margin_std"]) / float(np.std(margin))
    y = (np.random.default_rng([data_seed, 2]).random(n)
         < 1.0 / (1.0 + np.exp(-slope * margin))).astype(np.float32)

    rows_v, cols_v, vals_v, margin_v = _split(
        np.random.default_rng([data_seed, 3]), n_val, d, law, w_true)
    y_v = (np.random.default_rng([data_seed, 4]).random(n_val)
           < 1.0 / (1.0 + np.exp(-slope * margin_v))).astype(np.float32)
    # --seed: where each validation row stands, nothing else
    place = np.random.default_rng([int(seed), 0]).permutation(n_val)
    order = np.argsort(place[rows_v].astype(np.int64) * d + cols_v)
    y_val = np.empty_like(y_v)
    y_val[place] = y_v
    return {"rows": rows, "cols": cols, "vals": vals, "y": y,
            "rows_val": place[rows_v][order].astype(np.int32),
            "cols_val": cols_v[order], "vals_val": vals_v[order], "y_val": y_val}


def hot_coverage(cols: np.ndarray, hot_cols: int) -> float:
    """Share of the entries in the ``hot_cols`` columns that hold most
    (``np.unique`` over the entries, as the layout's builder ranks them)."""
    _, counts = np.unique(cols, return_counts=True)
    counts.sort()
    return float(counts[-hot_cols:].sum() / len(cols))
