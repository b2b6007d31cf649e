"""Generator of the ratings data set of ``game-ymusic-r2``: explicit ratings 1
to 5 of songs by users, every song by one artist, under a true mixed-effect
model (fixed effect + per-user + per-song + per-artist).

The rules are ``datagen.make_glmix``'s (its helpers are imported, not
copied): the configuration fixes the STRUCTURE (rows, entity counts, every
entity's size ``clip(round(c / rank^a), min, max)`` summing exactly to the
rows, non-zeros a row, so every ``[e, cap, d]`` block and every compiled
program), the configuration's ``data_seed`` fixes the VALUES (features, true
coefficients, the noise, the training rows' order, which song is whose), and
``seed`` names the entities and orders the validation rows, no more: every
seed poses the same fit, entity for entity.

What differs from the GLMix generator:

* THREE entity columns. ``user`` and ``song`` are drawn as there (a row's
  user and its song by two independent shuffles of the sizes' repeats);
  ``artist`` is a FUNCTION of the song: ``artists.count`` artists own
  ``size_profile(count, songs, a, min, max)`` songs each, the songs handed out
  by one seeded shuffle of the popularity ranks (an artist's songs are DRAWN
  over the ranks, not contiguous in them), so an artist's rows are the sum of
  its songs' and the head artists lie far over the ladder's top rung.
* TWO coordinates read ONE block: the per-song and the per-artist effect are
  both linear in ``x_item`` (the item-side ``[n, d_e + 1]`` block), each with
  a table of its own.
* The label is a RATING: ``clip(round(3 + margin + noise), 1, 5)`` with
  Gaussian noise of the configured deviation, one of the five published
  values and exact in float32.
* The validation rows are 10 A USER (the published test part's shape): for
  every user ten of its training rows, evenly spaced among them, lend their
  (user, song) pair to a fresh row (new features, new noise).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.datagen import (
    CHUNK_ROWS,
    GEN_THREADS,
    _distinct_columns,
    _ranks_of_rows,
    entity_sizes,
    size_profile,
)

#: the three entity columns, in the configuration's order of update, with the
#: key of their block in the configuration
ENTITIES = (("user", "users"), ("song", "songs"), ("artist", "artists"))
#: the terms of the true margin that are linear in each feature block
TERMS_OF = {"x_global": ("fe",), "x_user": ("user",), "x_item": ("song", "artist")}
VALIDATION_PER_USER = 10


def artist_of_song(cfg: dict) -> np.ndarray:
    """[songs] int32: the artist's size rank for every song's popularity rank.
    Artist k owns ``sizes[k]`` songs (``artists``' profile over the songs);
    which ones is one shuffle of the ranks from ``data_seed``."""
    songs = int(cfg["songs"]["count"])
    a = cfg["artists"]
    per_artist = size_profile(int(a["count"]), songs, float(a["a"]),
                              int(a["min"]), int(a["max"]))
    rng = np.random.default_rng([int(cfg["data_seed"]), 4])
    return np.repeat(np.arange(len(per_artist), dtype=np.int32),
                     per_artist)[rng.permutation(songs)]


def true_model(cfg: dict) -> dict:
    """The true coefficients by size rank: {"fe": [d_g + 1], "user" | "song" |
    "artist": [count, d_e + 1]}, float32, the intercepts in the last column."""
    w, t = cfg["widths"], cfg["truth"]
    d_g, d_e = int(w["global_features"]), int(w["entity_features"])
    rng = np.random.default_rng([int(cfg["data_seed"]), 0])
    out = {"fe": np.append(
        rng.normal(scale=float(t["global_scale"]), size=d_g), 0.0).astype(np.float32)}
    for name, key in ENTITIES:
        count = int(cfg[key]["count"])
        out[name] = np.column_stack([
            rng.normal(scale=float(t["entity_scale"]), size=(count, d_e)),
            rng.normal(scale=float(t["entity_bias_scale"]), size=count),
        ]).astype(np.float32)
    return out


def _validation_picks(user_rank: np.ndarray, user_sizes: np.ndarray) -> np.ndarray:
    """[10 * users] training rows, ten of every user's, evenly spaced among
    the user's rows in their canonical order."""
    by_user = np.argsort(user_rank, kind="stable")
    start = np.concatenate(([0], np.cumsum(user_sizes)[:-1]))
    step = np.arange(VALIDATION_PER_USER, dtype=np.int64)
    within = (step[None, :] * user_sizes[:, None]) // VALIDATION_PER_USER
    return by_user[(start[:, None] + within).ravel()]


def make_ratings(cfg: dict, seed: int) -> dict:
    """Host arrays of one ratings data set at the configuration's shape:
    ``{"train": split, "validation": split, "user_sizes", "song_sizes",
    "artist_sizes"}``; a split holds dense ``x_global [n, d_g + 1]``,
    ``x_user``, ``x_item`` ``[n, d_e + 1]`` (last column the intercept),
    ``y`` (float32, one of 1 to 5) and int32 ``user``, ``song``, ``artist``."""
    w = cfg["widths"]
    d_g, d_e = int(w["global_features"]), int(w["entity_features"])
    k_g, k_e = int(w["global_nnz"]), int(w["entity_nnz"])
    n, n_val = int(cfg["rows"]), int(cfg["validation_rows"])
    data_seed = int(cfg["data_seed"])
    noise = float(cfg["truth"]["noise"])
    user_sizes = entity_sizes(cfg["users"], n)
    song_sizes = entity_sizes(cfg["songs"], n)
    if n_val != VALIDATION_PER_USER * len(user_sizes):
        raise ValueError(f"validation_rows {n_val} is not 10 a user")
    artist_by_song = artist_of_song(cfg)
    truth = true_model(cfg)

    ranks = np.random.default_rng([data_seed, 1])
    user_rank = _ranks_of_rows(ranks, user_sizes)
    song_rank = _ranks_of_rows(ranks, song_sizes)
    pick = _validation_picks(user_rank, user_sizes)

    layout = np.random.default_rng([seed, 0])
    ids = {name: layout.permutation(int(cfg[key]["count"])).astype(np.int32)
           for name, key in ENTITIES}

    def split(stream: int, rows: int, u_rank: np.ndarray, s_rank: np.ndarray,
              reorder: bool) -> dict:
        position = layout.permutation(rows) if reorder else np.arange(rows)
        out = {
            "x_global": np.zeros((rows, d_g + 1), np.float32),
            "x_user": np.zeros((rows, d_e + 1), np.float32),
            "x_item": np.zeros((rows, d_e + 1), np.float32),
            "y": np.zeros(rows, np.float32),
            **{name: np.zeros(rows, np.int32) for name, _ in ENTITIES},
        }

        def fill(chunk: int) -> None:
            lo, hi = chunk * CHUNK_ROWS, min(rows, (chunk + 1) * CHUNK_ROWS)
            m = hi - lo
            rng = np.random.default_rng([data_seed, stream, chunk])
            at = position[lo:hi]
            rank = {"user": u_rank[lo:hi], "song": s_rank[lo:hi]}
            rank["artist"] = artist_by_song[rank["song"]]
            margin = np.zeros(m, np.float32)
            for name, d, k in (("x_global", d_g, k_g), ("x_user", d_e, k_e),
                               ("x_item", d_e, k_e)):
                cols = _distinct_columns(rng, m, d, k)
                vals = rng.standard_normal((m, k), dtype=np.float32)
                out[name][at[:, None], cols] = vals
                out[name][at, d] = 1.0
                for term in TERMS_OF[name]:
                    table = (np.broadcast_to(truth["fe"], (m, d + 1)) if term == "fe"
                             else truth[term][rank[term]])
                    margin += (vals * np.take_along_axis(table, cols, axis=1)
                               ).sum(1) + table[:, d]
            rating = 3.0 + margin.astype(np.float64) + noise * rng.standard_normal(m)
            out["y"][at] = np.clip(np.round(rating), 1.0, 5.0)
            for entity in rank:
                out[entity][at] = ids[entity][rank[entity]]

        chunks = range((rows + CHUNK_ROWS - 1) // CHUNK_ROWS)
        with ThreadPoolExecutor(GEN_THREADS) as pool:
            list(pool.map(fill, chunks))
        return out

    return {
        "train": split(2, n, user_rank, song_rank, reorder=False),
        "validation": split(3, n_val, user_rank[pick], song_rank[pick], reorder=True),
        "user_sizes": user_sizes, "song_sizes": song_sizes,
        "artist_sizes": np.bincount(artist_by_song[song_rank],
                                    minlength=int(cfg["artists"]["count"])),
    }
