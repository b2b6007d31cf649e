"""Generator of a dense two-class data set at a configuration's shape, made
ON THE DEVICE in one jitted call (a 400,000 x 2,000 block is 3.2 GB: drawn
on the host it would be most of a run's set-up).

The configuration fixes the shape and, through its ``data_seed``, every
value the FIT sees: the training rows, their order, their labels. ``seed``
(the run's ``--seed``) decides the order of the VALIDATION rows and nothing
else, for the reason ``datagen.make_glmix`` gives: float32 line searches
turn on rounding, so a seed that reached the training rows would change the
amount of work, not only the values. The program receives arrays, never a
seed.

What is drawn (``assumed`` in the configuration file says why): columns
that are Gaussian and correlated through ``latent_factors`` shared factors
carrying ``factor_share`` of every column's variance; then, as the source
prepares its data, every column standardized by the TRAINING rows' sample
mean and deviation and every row scaled to unit length; labels Bernoulli of
a seeded true model whose margins have the deviation ``true_margin_std``.
"""

from __future__ import annotations

import numpy as np

#: rows drawn per step of the on-device loop: bounds the temporaries beside
#: the block being filled (a step's draws depend on (data_seed, split, step))
CHUNK_ROWS = 50_000


def _chunks(rows: int) -> tuple[int, int]:
    """(steps, rows per step): one step for a small block, else whole steps
    of CHUNK_ROWS."""
    if rows <= CHUNK_ROWS:
        return 1, rows
    if rows % CHUNK_ROWS:
        raise ValueError(f"{rows} rows are not a multiple of {CHUNK_ROWS}")
    return rows // CHUNK_ROWS, CHUNK_ROWS


def make_dense(cfg: dict, seed: int, device) -> dict:
    """{"x" [n, d], "y" [n], "x_val" [n_val, d], "y_val" [n_val]}: float32
    arrays resident on ``device``."""
    import jax
    import jax.numpy as jnp

    n, n_val, d = int(cfg["rows"]), int(cfg["validation_rows"]), int(cfg["features"])
    k = int(cfg["latent_factors"])
    share = float(cfg["factor_share"])
    margin_std = float(cfg["true_margin_std"])
    f32 = jnp.float32

    def generate(key, val_order):
        k_load, k_true, k_train, k_val = jax.random.split(key, 4)
        loadings = jax.random.normal(k_load, (k, d), f32)
        loadings = loadings / jnp.linalg.norm(loadings, axis=0, keepdims=True)
        w_true = jax.random.normal(k_true, (d,), f32)
        # deviation of u.w for a standardized row u: w'Cw, C = (1-share) I + share L'L
        spread = jnp.sqrt((1.0 - share) * jnp.vdot(w_true, w_true)
                          + share * jnp.sum((loadings @ w_true) ** 2))
        slope = margin_std * jnp.sqrt(f32(d)) / spread

        def raw(split_key, step, rows):
            k_z, k_g = jax.random.split(jax.random.fold_in(split_key, step))
            z = jax.random.normal(k_z, (rows, d), f32)
            g = jax.random.normal(k_g, (rows, k), f32)
            return (jnp.sqrt(f32(1.0 - share)) * z
                    + jnp.sqrt(f32(share)) * jnp.dot(
                        g, loadings, precision=jax.lax.Precision.HIGHEST))

        steps, rows = _chunks(n)

        def moments(step):
            u = raw(k_train, step, rows)
            return jnp.sum(u, axis=0), jnp.sum(u * u, axis=0)

        sums, squares = jax.lax.map(moments, jnp.arange(steps))
        mean = jnp.sum(sums, axis=0) / n
        deviation = jnp.sqrt(jnp.sum(squares, axis=0) / n - mean * mean)

        def block(split_key, step, rows):
            u = (raw(split_key, step, rows) - mean) / deviation
            x = u / jnp.linalg.norm(u, axis=1, keepdims=True)
            margin = slope * jnp.sum(x * w_true, axis=1)
            coin = jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(split_key, step), 1), (rows,), f32)
            return x, (coin < jax.nn.sigmoid(margin)).astype(f32)

        def split(split_key, total):
            steps, rows = _chunks(total)
            x, y = jax.lax.map(lambda s: block(split_key, s, rows), jnp.arange(steps))
            return x.reshape(total, d), y.reshape(total)

        x, y = split(k_train, n)
        x_val, y_val = split(k_val, n_val)
        return {"x": x, "y": y, "x_val": x_val[val_order], "y_val": y_val[val_order]}

    val_order = np.random.default_rng([int(seed), 0]).permutation(n_val).astype(np.int32)
    with jax.default_device(device):
        key = jax.random.key(int(cfg["data_seed"]))
        return jax.jit(generate)(key, jax.device_put(val_order, device))
