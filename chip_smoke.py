#!/usr/bin/env python3
"""chip_smoke.py — does the main path still start, and come out right, on the chip?

One process drives, in order, through the entry points a user would call:

  kernel   ops.pallas_glm.fused_value_and_gradient vs the autodiff objective
           at d in {256, 512, 2048, 4096, 16384} x {f32, bf16} on whole tiles
           and at d = 2000, 617 rows short of them (the masked body; since
           PR 53 the per-row columns reach both kernels as one [3, n] block,
           whose last (3, tile) piece is then ragged along the lanes, and
           16384 is the width whose float32 tile is the rule's floor of 128
           rows, 8 MiB): Mosaic custom call present, value/gradient agree
           with an f64 numpy recomputation; then
           ops.pallas_glm.fused_hessian_vector at each: its custom call, the
           product against f64 numpy and against the jvp of the gradient
  glmix    cli.game_training_driver.main  (TrainingExampleAvro on disk ->
           FE d=256 + per-user RE + per-item RE d=16, logistic, fused
           GameTrainProgram, 2 CD sweeps, telemetry on, 0 restarts)
  score    cli.game_scoring_driver.main   (validation files, out/best)
  serve    cli.serve_driver.main          (>= 64 requests of the same rows)
  glm      cli.glm_driver.main x 2        (LibSVM n=262144 d=512: one lambda
           -> the Pallas kernel; --grid-parallel over 4 -> the vmapped XLA path)

and holds what comes out against plain numpy: the saved model's training
loss, one finite score per row equal to the numpy margin, served == batch
scores, validation AUC against the generator's own oracle AUC. Data is made
from --seed by a vectorised generator; nothing is downloaded; no child
process touches JAX (the chip belongs to the process that first touches it).

It REFUSES to run without a TPU (exit 2, no result line). With
--rehearse-cpu it runs the same legs at tiny size on the CPU backend (kernel
interpreted) to debug the script itself; that mode prints REHEARSAL on every
result and never prints an "ok" field.

On a host with 4 chips the glmix+score legs repeat per mesh (1 chip, then
data=4,model=1, then data=2,model=2) and the saved models are compared.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
A full report goes to chiprun_out/chip_smoke-<N>chip.json.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, ".chip_smoke")  # git-ignored, wiped per run
REPORT_DIR = os.path.join(HERE, "chiprun_out")  # git-ignored, copied back

EXIT_FAILED, EXIT_NO_CHIP = 1, 2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Depth (rows, entities) may be cut; widths never are."""

    n_train: int = 131072
    n_val: int = 16384
    n_users: int = 2000
    n_items: int = 1500
    glm_n: int = 262144
    glm_n_val: int = 16384
    kernel_widths: tuple = (256, 512, 2048, 4096, 16384)
    kernel_tiles: int = 8
    #: (width, rows short of ``kernel_tiles`` whole tiles): neither a whole
    #: number of lanes nor of row tiles, so Mosaic compiles the masked body
    kernel_ragged: tuple = (2000, 617)
    serve_requests: int = 64
    serve_request_rows: int = 16
    # widths: FE 255 features + intercept, RE 15 + intercept, GLM 511 + intercept
    d_global: int = 255
    d_entity: int = 15
    nnz_global: int = 32
    nnz_entity: int = 8
    glm_d: int = 511
    glm_nnz: int = 32
    #: validation AUC must recover this share of the generator's own oracle
    #: lift over 0.5 (the oracle scores with the TRUE coefficients)
    auc_lift_floor: float = 0.75


FULL = Sizes()
REHEARSAL = Sizes(
    n_train=4096, n_val=1024, n_users=60, n_items=40, glm_n=4096,
    glm_n_val=1024, kernel_widths=(256, 512), kernel_tiles=2,
    kernel_ragged=(200, 617),
    auc_lift_floor=0.5,  # 4096 rows cannot pin 256 + 100 x 16 coefficients
)


class Checks:
    """Every assertion is printed as it is made; failures are collected so
    one run reports all of them."""

    def __init__(self, tag: str):
        self.tag = tag
        self.failed: list[str] = []
        self.report: dict = {}

    def check(self, name: str, ok: bool, detail="") -> bool:
        print(f"{self.tag}[{'ok' if ok else 'FAIL'}] {name}: {detail}",
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def note(self, name: str, value) -> None:
        print(f"{self.tag}[info] {name}: {value}", flush=True)
        self.report[name] = value


# ---------------------------------------------------------------------------
# data: vectorised generators (numpy byte scatters, no per-record Python)
# ---------------------------------------------------------------------------

def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """[...] non-negative ints -> [..., width] zero-padded ASCII digits."""
    pw = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((values[..., None].astype(np.int64) // pw) % 10 + 48).astype(np.uint8)


def _distinct_columns(rng, n: int, d: int, k: int) -> np.ndarray:
    """[n, k] column ids, distinct within each row: a window of a fixed
    random permutation of range(d), at a per-row random offset."""
    perm = rng.permutation(d)
    start = rng.integers(0, d, size=n)
    return perm[(start[:, None] + np.arange(k)[None, :]) % d]


def _zipf_ids(rng, n: int, num: int) -> np.ndarray:
    """Entity ids with Zipfian (1/rank) sizes."""
    p = 1.0 / np.arange(1, num + 1)
    return rng.choice(num, size=n, p=p / p.sum())


GLMIX_SCHEMA = {
    "name": "TrainingExampleAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "type": "record",
    "fields": [
        {"name": "uid", "type": ["string", "null"]},
        {"name": "label", "type": "double"},
        {"name": "features", "type": {"type": "array", "items": {
            "name": "FeatureAvro", "type": "record", "fields": [
                {"name": "name", "type": "string"},
                {"name": "term", "type": "string"},
                {"name": "value", "type": "double"}]}}},
        {"name": "userFeatures", "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "itemFeatures", "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "weight", "type": ["double", "null"]},
        {"name": "offset", "type": ["double", "null"]},
        {"name": "metadataMap", "type": [{"type": "map", "values": "string"}, "null"]},
    ],
}


def _f64_bytes(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype="<f8").view(np.uint8).reshape(*a.shape, 8)


def _encode_bag(prefix: str, cols: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Avro array<FeatureAvro> for every row at once: names are the prefix
    letter + 3 digits, terms empty. All lengths < 64, so every varint is one
    byte and every row encodes to the same width."""
    n, k = cols.shape
    item = np.zeros((n, k, 14), np.uint8)
    item[:, :, 0] = 4 << 1  # zigzag(len("g123"))
    item[:, :, 1] = ord(prefix)
    item[:, :, 2:5] = _digits(cols, 3)
    item[:, :, 5] = 0  # term ""
    item[:, :, 6:14] = _f64_bytes(vals)
    out = np.zeros((n, 1 + 14 * k + 1), np.uint8)
    out[:, 0] = k << 1  # one block of k items ...
    out[:, 1:-1] = item.reshape(n, -1)  # ... then the 0 end marker
    return out


def _encode_string(prefix: bytes, digits: np.ndarray) -> np.ndarray:
    n = digits.shape[0]
    body = np.concatenate(
        [np.broadcast_to(np.frombuffer(prefix, np.uint8), (n, len(prefix))),
         digits], axis=1)
    return np.concatenate(
        [np.full((n, 1), body.shape[1] << 1, np.uint8), body], axis=1)


def _encode_const(n: int, raw: bytes) -> np.ndarray:
    return np.broadcast_to(np.frombuffer(raw, np.uint8), (n, len(raw)))


def make_glmix(sizes: Sizes, seed: int, out_dir: str) -> dict:
    """Write train/ and val/ TrainingExampleAvro dirs; return the arrays a
    numpy recomputation needs (never read back from the files)."""
    from photon_ml_tpu.io import avro as avro_io

    truth = np.random.default_rng([seed, 0])
    w_g = truth.normal(scale=0.25, size=sizes.d_global)
    b_g = -0.3
    w_u = truth.normal(scale=0.3, size=(sizes.n_users, sizes.d_entity))
    b_u = truth.normal(scale=0.5, size=sizes.n_users)
    w_i = truth.normal(scale=0.3, size=(sizes.n_items, sizes.d_entity))
    b_i = truth.normal(scale=0.5, size=sizes.n_items)
    splits = {}
    for s, (split, n) in enumerate((("train", sizes.n_train),
                                    ("val", sizes.n_val))):
        rng = np.random.default_rng([seed, 1 + s])
        user = _zipf_ids(rng, n, sizes.n_users)
        item = _zipf_ids(rng, n, sizes.n_items)
        cg = _distinct_columns(rng, n, sizes.d_global, sizes.nnz_global)
        cu = _distinct_columns(rng, n, sizes.d_entity, sizes.nnz_entity)
        ci = _distinct_columns(rng, n, sizes.d_entity, sizes.nnz_entity)
        vg = rng.normal(size=cg.shape)
        vu = rng.normal(size=cu.shape)
        vi = rng.normal(size=ci.shape)
        margin = (
            (vg * w_g[cg]).sum(1) + b_g
            + (vu * w_u[user[:, None], cu]).sum(1) + b_u[user]
            + (vi * w_i[item[:, None], ci]).sum(1) + b_i[item]
        )
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float64)
        one = _encode_const(n, b"\x00" + np.float64(1.0).tobytes())
        zero = _encode_const(n, b"\x00" + np.float64(0.0).tobytes())
        rows = np.concatenate([
            _encode_const(n, b"\x00"),  # uid: union branch 0 (string)
            _encode_string(b"", _digits(np.arange(n), 7)),
            _f64_bytes(y),
            _encode_bag("g", cg, vg),
            _encode_bag("u", cu, vu),
            _encode_bag("i", ci, vi),
            one,  # weight
            zero,  # offset
            _encode_const(n, b"\x00\x04"),  # metadataMap: branch 0, 2 entries
            _encode_const(n, b"\x0cuserId"),
            _encode_string(b"u", _digits(user, 4)),
            _encode_const(n, b"\x0citemId"),
            _encode_string(b"i", _digits(item, 4)),
            _encode_const(n, b"\x00"),  # map end
        ], axis=1)
        os.makedirs(os.path.join(out_dir, split), exist_ok=True)
        block = 8192
        avro_io.write_container_blocks(
            os.path.join(out_dir, split, "part-00000.avro"), GLMIX_SCHEMA,
            ((len(rows[lo:lo + block]), rows[lo:lo + block].tobytes())
             for lo in range(0, n, block)),
        )
        splits[split] = dict(user=user, item=item, cg=cg, cu=cu, ci=ci,
                             vg=vg, vu=vu, vi=vi, y=y, oracle_margin=margin)
    return splits


def make_libsvm(sizes: Sizes, seed: int, out_dir: str) -> dict:
    """a1a-shaped LibSVM text (sorted 1-based indices, ~32 nnz/row), built
    as one fixed-width byte matrix."""
    truth = np.random.default_rng([seed, 10])
    w = truth.normal(scale=0.3, size=sizes.glm_d)
    splits = {}
    for s, (split, n) in enumerate((("train", sizes.glm_n),
                                    ("val", sizes.glm_n_val))):
        rng = np.random.default_rng([seed, 11 + s])
        cols = np.sort(
            _distinct_columns(rng, n, sizes.glm_d, sizes.glm_nnz), axis=1)
        q = rng.integers(1, 10000, size=cols.shape)  # value = q / 10000
        vals = q / 10000.0
        margin = (vals * w[cols]).sum(1) - 0.2
        y = rng.random(n) < 1.0 / (1.0 + np.exp(-margin))
        tok = np.full((n, sizes.glm_nnz, 11), ord(" "), np.uint8)
        idx = _digits(cols + 1, 3)
        idx[(cols + 1 < 100)[..., None] & (np.arange(3) == 0)] = ord(" ")
        idx[(cols + 1 < 10)[..., None] & (np.arange(3) <= 1)] = ord(" ")
        tok[:, :, 1:4] = idx
        tok[:, :, 4] = ord(":")
        tok[:, :, 5:7] = np.frombuffer(b"0.", np.uint8)
        tok[:, :, 7:11] = _digits(q, 4)
        line = np.full((n, 2 + 11 * sizes.glm_nnz + 1), ord(" "), np.uint8)
        line[:, 0] = np.where(y, ord("+"), ord("-"))
        line[:, 1] = ord("1")
        line[:, 2:-1] = tok.reshape(n, -1)
        line[:, -1] = ord("\n")
        os.makedirs(os.path.join(out_dir, split), exist_ok=True)
        with open(os.path.join(out_dir, split, "part-00000.libsvm"), "wb") as f:
            f.write(line.tobytes())
        splits[split] = dict(cols=cols, vals=vals, y=y.astype(np.float64),
                             oracle_margin=margin)
    return splits


# ---------------------------------------------------------------------------
# plain numpy references
# ---------------------------------------------------------------------------

def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    from scipy.stats import rankdata

    ranks = rankdata(scores)  # average ranks over ties
    pos = labels > 0.5
    n1, n0 = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def logistic_loss(margin: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.logaddexp(0.0, margin) - y * margin))


def read_saved_glmix(model_dir: str, sizes: Sizes) -> dict:
    """The saved model's coefficients, parsed from its Avro records by
    feature NAME (the generator's own naming) — not through model_io."""
    from photon_ml_tpu.io import avro as avro_io

    def vec(record, d):
        w = np.zeros(d + 1)  # last slot: intercept
        for f in record["means"]:
            j = d if f["name"] == "(INTERCEPT)" else int(f["name"][1:])
            w[j] = f["value"]
        return w

    (fe,) = avro_io.read_directory(
        os.path.join(model_dir, "fixed-effect", "global", "coefficients"))
    out = {"global": vec(fe, sizes.d_global)}
    for coord, num in (("per-user", sizes.n_users), ("per-item", sizes.n_items)):
        table = np.zeros((num, sizes.d_entity + 1))  # unseen entity: zeros
        for r in avro_io.read_directory(
                os.path.join(model_dir, "random-effect", coord, "coefficients")):
            table[int(r["modelId"][1:])] = vec(r, sizes.d_entity)
        out[coord] = table
    return out


def glmix_margin(model: dict, rows: dict) -> np.ndarray:
    g, u, i = model["global"], model["per-user"], model["per-item"]
    user, item = rows["user"], rows["item"]
    return (
        (rows["vg"] * g[rows["cg"]]).sum(1) + g[-1]
        + (rows["vu"] * u[user[:, None], rows["cu"]]).sum(1) + u[user, -1]
        + (rows["vi"] * i[item[:, None], rows["ci"]]).sum(1) + i[item, -1]
    )


# ---------------------------------------------------------------------------
# what the drivers recorded about themselves
# ---------------------------------------------------------------------------

class DriverRecord:
    """Checks every leg makes against a driver's own summary + journal."""

    def __init__(self, checks: Checks, platform: str):
        self.c = checks
        self.platform = platform
        self._kernel_traces = {"compiled": 0, "interpreted": 0}

    def runtime(self, leg: str, summary: dict, decode: dict) -> None:
        rt = summary["runtime"]
        self.c.check(
            f"{leg}: summary stamps the platform", rt["platform"] == self.platform,
            f"{rt['platform']} / {rt['device_kind']} x{rt['device_count']}, "
            f"jax {rt['jax_version']} jaxlib {rt['jaxlib_version']} "
            f"libtpu {rt['libtpu_version']}")
        self.c.check(f"{leg}: native decoders", summary["decode_paths"] == decode,
                     summary["decode_paths"])

    def journal(self, leg: str, telemetry_dir: str) -> tuple:
        """Read a leg's run journal: no restart / retry / quarantine may have
        happened; phase seconds and compile totals go to the report.
        Returns (rows, registry snapshot)."""
        from photon_ml_tpu.telemetry.journal import read_journal

        rows = read_journal(os.path.join(telemetry_dir, "run-journal.jsonl"))
        (snap,) = [r["snapshot"] for r in rows if r["kind"] == "metrics"]
        bad_rows = [r["kind"] for r in rows if r["kind"] in (
            "resilience_restart", "run_failure", "quarantined_block")]
        counters = {k: v for k, v in snap["counters"].items()
                    if k.startswith("resilience/")}
        self.c.check(
            f"{leg}: 0 restarts / retries / quarantines",
            not bad_rows and not any(counters.values()),
            f"rows {bad_rows} counters {counters}")
        self.c.note(f"{leg}: driver phase seconds (host clock)", {
            r["name"]: round(r["total"], 2)
            for r in rows if r["kind"] == "phase_timing"})
        hist = snap["histograms"].get("jax/backend_compile_seconds", {})
        self.c.note(f"{leg}: backend compiles (process so far)", {
            "count": snap["counters"].get("jax/backend_compile_count", 0),
            "seconds": round(hist.get("total", 0.0), 2)})
        return rows, snap

    def mark_kernel_traces(self) -> None:
        """Call before a driver leg: the trace counters are process-wide and
        monotone, so a leg's own traces are its journal's snapshot minus
        what the process had counted when the leg began."""
        from photon_ml_tpu.telemetry.registry import default_registry

        self._kernel_traces = {
            k: default_registry().counter(f"ops/pallas_glm/traces_{k}").value
            for k in self._kernel_traces}

    def kernel_traces(self, snap: dict) -> dict:
        return {k: snap["counters"].get(f"ops/pallas_glm/traces_{k}", 0) - v
                for k, v in self._kernel_traces.items()}


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_kernel(c: Checks, sizes: Sizes, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.pallas_glm import (
        _round_up,
        _row_tile,
        fused_hessian_vector,
        fused_value_and_gradient,
    )

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    loss = LogisticLoss()
    reference = GLMObjective(loss, l2_weight=0.5, use_pallas=False)
    shapes = [(d, 0) for d in sizes.kernel_widths] + [sizes.kernel_ragged]
    for d, rows_short in shapes:
        for dtype in (jnp.float32, jnp.bfloat16):
            tile = _row_tile(_round_up(d, 128), jnp.dtype(dtype).itemsize)
            n = sizes.kernel_tiles * tile - rows_short
            name = f"kernel d={d} {jnp.dtype(dtype).name} n={n}"
            rng = np.random.default_rng(d)
            w = rng.normal(size=d).astype(np.float32)
            y = (rng.random(n) < 0.5).astype(np.float32)
            x = jnp.asarray(
                rng.normal(size=(n, d)).astype(np.float32) / np.sqrt(d), dtype)
            batch = LabeledPointBatch(x, jnp.asarray(y), jnp.zeros(n, jnp.float32),
                                      jnp.ones(n, jnp.float32))
            # f64 truth on the features as stored (bf16-rounded or not)
            x64 = np.asarray(x.astype(jnp.float32), np.float64)
            m = x64 @ w.astype(np.float64)
            v_true = np.sum(np.logaddexp(0, m) - y * m) + 0.25 * float(w @ w)
            g_true = x64.T @ (1 / (1 + np.exp(-m)) - y) + 0.5 * w
            compiled = jax.jit(
                lambda w_, b_: fused_value_and_gradient(loss, w_, b_, l2_weight=0.5)
            ).lower(jnp.asarray(w), batch).compile()
            if on_tpu:
                c.check(f"{name}: Mosaic custom call in the compiled program",
                        "tpu_custom_call" in compiled.as_text(), f"n={n}")
            v, g = compiled(jnp.asarray(w), batch)
            rv, rg = jax.jit(jax.value_and_grad(reference.value))(
                jnp.asarray(w), batch)
            # f32 X: 1e-4 of truth. bf16 X: the kernel multiplies the stored
            # bf16 values in f32, so it is held to the same 1e-4 of truth; the
            # XLA reference also rounds w to bf16 (ops/objective.py margins),
            # so against IT the band is the documented bf16 one.
            c.check(f"{name}: kernel vs f64 numpy (value, grad)",
                    rel(v, v_true) < 1e-4 and rel(g, g_true) < 1e-4,
                    f"{rel(v, v_true):.2e}, {rel(g, g_true):.2e}")
            band = 1e-4 if dtype == jnp.float32 else 5e-3
            c.check(f"{name}: kernel vs autodiff objective (value, grad)",
                    rel(v, rv) < band and rel(g, rg) < band,
                    f"{rel(v, rv):.2e}, {rel(g, rg):.2e} (band {band:g})")
            # a Hessian-vector product at the same width: the kernel's sibling
            vec = rng.normal(size=d).astype(np.float32)
            p = 1 / (1 + np.exp(-m))
            hv_true = x64.T @ (p * (1 - p) * (x64 @ vec.astype(np.float64))) + 0.5 * vec
            operands = (jnp.asarray(w), jnp.asarray(vec), batch)
            product = jax.jit(
                lambda w_, v_, b_: fused_hessian_vector(loss, w_, v_, b_, l2_weight=0.5)
            ).lower(*operands).compile()
            if on_tpu:
                c.check(f"{name}: product, Mosaic custom call in the compiled program",
                        "tpu_custom_call" in product.as_text(), f"n={n}")
            hv = product(*operands)
            rhv = jax.jit(reference.hessian_vector)(*operands)
            c.check(f"{name}: product vs f64 numpy", rel(hv, hv_true) < 1e-4,
                    f"{rel(hv, hv_true):.2e}")
            c.check(f"{name}: product vs the jvp of the gradient",
                    rel(hv, rhv) < band, f"{rel(hv, rhv):.2e} (band {band:g})")
    _check_placed_batch(c, sizes, loss, on_tpu)


def _check_placed_batch(c: Checks, sizes: Sizes, loss, on_tpu: bool) -> None:
    """PR 49: a block the chip keeps COLUMN-major (``kernel_ragged``'s width
    over whole row tiles) is moved by ``LabeledPointBatch.create``, once, to
    lie as the kernels read it; value, gradient and a whole ``glm/path_solve``
    are the as-made block's bit for bit, and the program compiled for the
    placed block copies no block of its shape where the as-made one's does.
    Every program here goes to the persistent compile cache (floor 0 s, as
    ``benchmark/run.py`` sets it), so that a SECOND run of this leg over one
    cache loads them: that is where ``jax.device_put`` to a ``Format`` broke."""
    import re

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import estimators
    from photon_ml_tpu.data.batch import DENSE_RELAYOUTS, LabeledPointBatch
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.pallas_glm import _round_up, _row_tile
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
    from photon_ml_tpu.telemetry.registry import default_registry

    d, _ = sizes.kernel_ragged
    n = sizes.kernel_tiles * _row_tile(_round_up(d, 128), 4)
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) / np.sqrt(d))
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    name = f"placed batch d={d} n={n}"
    relayouts = default_registry().counter(DENSE_RELAYOUTS)
    floor = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, floor)
    jax.config.update(floor, 0.0)
    try:
        before = relayouts.value
        placed = LabeledPointBatch.create(x, y)
        moved = relayouts.value - before
        as_made = placed.replace(features=x)
        lies = [tuple(a.format.layout.major_to_minor) for a in (x, placed.features)]
        if on_tpu:
            c.check(f"{name}: the chip keeps the block column-major, create moved it once",
                    lies == [(1, 0), (0, 1)] and placed.features is not x and moved == 1,
                    f"as made {lies[0]}, placed {lies[1]}, relayouts +{moved}")
        else:
            c.check(f"{name}: already row-major, create moved nothing",
                    lies[1] == (0, 1) and placed.features is x and moved == 0,
                    f"as made {lies[0]}, relayouts +{moved}")
        objective = GLMObjective(loss)
        evaluate = jax.jit(objective.value_and_gradient)
        (v, g), (v_made, g_made) = evaluate(w, placed), evaluate(w, as_made)
        c.check(f"{name}: value and gradient == the as-made block's",
                bool(v == v_made) and bool(jnp.all(g == g_made)), f"value {float(v):.6g}")
        opt = OptimizerConfig(OptimizerType.LBFGS, max_iterations=5)
        args = (jnp.zeros_like(w), np.float32(1.0), None, None)
        solved, copies = {}, {}
        for side, batch in (("placed", placed), ("as made", as_made)):
            solved[side] = estimators._jitted_path_solve(objective, opt, batch, *args)
            copies[side] = len(re.findall(
                rf"= f32\[{n},{d}\][^ ]* copy\(",
                estimators._jitted_path_solve.lower(
                    objective, opt, batch, *args).compile().as_text()))
        c.check(f"{name}: glm/path_solve's coefficients == the as-made block's",
                bool(jnp.all(solved["placed"].coefficients == solved["as made"].coefficients)),
                f"iterations {int(solved['placed'].iterations)}")
        c.check(f"{name}: glm/path_solve copies no block of its shape",
                copies["placed"] == 0 and (copies["as made"] > 0 or not on_tpu),
                f"copies of f32[{n},{d}]: {copies}")
    finally:
        jax.config.update(floor, kept)


def glmix_argv(work: str, out: str, tel: str, mesh: "str | None") -> tuple:
    shards = [
        "name=global,feature.bags=features,intercept=true",
        "name=userShard,feature.bags=userFeatures,intercept=true",
        "name=itemShard,feature.bags=itemFeatures,intercept=true",
    ]
    argv = [
        "--input-data-path", os.path.join(work, "train"),
        "--validation-data-path", os.path.join(work, "val"),
        "--root-output-dir", out, "--override-output",
        "--task-type", "LOGISTIC_REGRESSION",
        # max.iter=10: the budget the GAME sweeps of this repo's records ran at
        "--coordinate-configurations",
        "name=global,feature.shard=global,reg.weights=1,max.iter=10",
        "--coordinate-configurations",
        "name=per-user,feature.shard=userShard,random.effect.type=userId,"
        "reg.weights=1,max.iter=10",
        "--coordinate-configurations",
        "name=per-item,feature.shard=itemShard,random.effect.type=itemId,"
        "reg.weights=1,max.iter=10",
        "--coordinate-descent-iterations", "2",
        "--evaluators", "AUC",
        "--telemetry-dir", tel,
        "--max-restarts", "0",
    ]
    for s in shards:
        argv += ["--feature-shard-configurations", s]
    argv += ["--mesh", mesh] if mesh else ["--distributed"]
    return argv, shards


def leg_glmix(c: Checks, rec: DriverRecord, sizes: Sizes, data: dict,
              work: str, mesh: "str | None", on_tpu: bool) -> dict:
    """train -> score (-> serve on the first mesh only); returns the saved
    model as numpy arrays for cross-mesh comparison."""
    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
    from photon_ml_tpu.io.model_io import read_scores

    tag = f"glmix[{mesh or 'distributed'}]"
    sub = os.path.join(work, "run-" + (mesh or "default").replace(",", "_"))
    out, tel = os.path.join(sub, "train"), os.path.join(sub, "train-telemetry")
    argv, shards = glmix_argv(work, out, tel, mesh)
    rec.mark_kernel_traces()
    t0 = time.perf_counter()
    summary = game_training_driver.main(argv)
    c.note(f"{tag}: train wall seconds", round(time.perf_counter() - t0, 1))
    with open(os.path.join(out, "training-summary.json")) as f:
        on_disk = json.load(f)
    rec.runtime(f"{tag} train", on_disk,
                {"train": "avro-native", "validation": "avro-native"})
    axes = {k: int(v) for k, v in (kv.split("=") for kv in mesh.split(","))} \
        if mesh else {"data": on_disk["runtime"]["device_count"], "model": 1}
    n_mesh = axes["data"] * axes["model"]
    c.check(f"{tag}: devices used", on_disk["runtime"]["devices_used"] == n_mesh,
            f"{on_disk['runtime']['devices_used']} of "
            f"{on_disk['runtime']['device_count']}")
    rows, snap = rec.journal(f"{tag} train", tel)
    traces = rec.kernel_traces(snap)
    if on_tpu and axes["model"] == 1:
        c.check(f"{tag}: FE solve holds the compiled kernel",
                traces["compiled"] >= 1 and traces["interpreted"] == 0, traces)
    else:
        # cpu rehearsal: the auto rule keeps the kernel for tpu; model>1
        # shards the FE feature axis, which the kernel does not take
        c.note(f"{tag}: kernel traces (not judged here)", traces)
    labels = [r.get("label") for r in rows if r["kind"] == "program_compile"]
    c.check(f"{tag}: ledger labelled the fused step",
            any(str(l).startswith("train/") for l in labels), sorted(set(labels)))
    gauges = snap["gauges"]
    for group in ("sample_arrays", "entity_arrays"):
        c.check(f"{tag}: {group} have shards on every mesh device",
                gauges.get(f"mesh/{group}/devices") == n_mesh,
                f"devices {gauges.get(f'mesh/{group}/devices')}, largest "
                f"shard {gauges.get(f'mesh/{group}/max_shard_fraction')}")
    mem = on_disk["runtime"]["device_memory"]
    c.note(f"{tag}: peak_bytes_in_use per device",
           [m["peak_bytes_in_use"] for m in mem])
    if on_tpu:
        c.check(f"{tag}: every mesh device held data (> 1 MiB peak)",
                sum(1 for m in mem if (m["peak_bytes_in_use"] or 0) > 1 << 20)
                >= n_mesh, f"{n_mesh} expected")

    history = summary["metric_history"][0]["metrics"]
    losses = [h["train:LOGISTIC_LOSS"] for h in history]
    c.check(f"{tag}: training loss falls sweep over sweep",
            all(np.isfinite(losses)) and all(
                b < a for a, b in zip(losses, losses[1:])) and len(losses) == 2,
            losses)
    val = data["val"]
    oracle = auc(val["oracle_margin"], val["y"])
    floor = 0.5 + sizes.auc_lift_floor * (oracle - 0.5)
    aucs = [h["validate:AUC"] for h in history]
    c.check(f"{tag}: validation AUC clears the floor",
            max(aucs) >= floor, f"{aucs} vs floor {floor:.4f} "
            f"({sizes.auc_lift_floor} of the generator's oracle lift, oracle AUC "
            f"{oracle:.4f})")

    model = read_saved_glmix(os.path.join(out, "best"), sizes)
    # out/best is the best-by-validation sweep; its loss is that sweep's
    best = int(np.argmax(aucs))
    ref_loss = logistic_loss(glmix_margin(model, data["train"]), data["train"]["y"])
    c.check(f"{tag}: driver train loss == numpy loss of the SAVED model",
            abs(losses[best] - ref_loss) <= 1e-3 * abs(ref_loss),
            f"driver {losses[best]:.6f} (sweep {best}) vs numpy {ref_loss:.6f}, "
            f"rel {abs(losses[best] - ref_loss) / abs(ref_loss):.2e}")

    # ---- score
    s_out, s_tel = os.path.join(sub, "score"), os.path.join(sub, "score-telemetry")
    s_argv = ["--input-data-path", os.path.join(work, "val"),
              "--model-input-dir", os.path.join(out, "best"),
              "--output-dir", s_out, "--evaluators", "AUC",
              "--telemetry-dir", s_tel]
    for s in shards:
        s_argv += ["--feature-shard-configurations", s]
    s_argv += ["--mesh", mesh] if mesh else ["--distributed"]
    t0 = time.perf_counter()
    game_scoring_driver.main(s_argv)
    c.note(f"{tag}: score wall seconds", round(time.perf_counter() - t0, 1))
    with open(os.path.join(s_out, "scoring-summary.json")) as f:
        s_disk = json.load(f)
    rec.runtime(f"{tag} score", s_disk, {"score": "avro-native"})
    rec.journal(f"{tag} score", s_tel)
    scored = read_scores(os.path.join(s_out, "scores"))
    batch = np.full(sizes.n_val, np.nan)
    for r in scored:
        batch[int(r["uid"])] = r["predictionScore"]
    c.check(f"{tag}: one finite score per input row",
            len(scored) == sizes.n_val and np.isfinite(batch).all(),
            f"{len(scored)} records for {sizes.n_val} rows")
    ref = glmix_margin(model, val)
    err = float(np.max(np.abs(batch - ref) / (1.0 + np.abs(ref))))
    c.check(f"{tag}: batch scores == numpy margins of the saved model",
            err < 1e-4, f"max |d|/(1+|ref|) = {err:.2e}")
    c.check(f"{tag}: scoring AUC == numpy AUC of those scores",
            abs(s_disk["evaluations"]["AUC"] - auc(batch, val["y"])) < 1e-3,
            f"{s_disk['evaluations']['AUC']:.5f} vs {auc(batch, val['y']):.5f}")
    return {"model": model, "numpy_loss": ref_loss, "batch_scores": batch,
            "best_dir": os.path.join(out, "best"), "shards": shards, "sub": sub}


def leg_serve(c: Checks, rec: DriverRecord, sizes: Sizes, work: str,
              glmix: dict) -> None:
    from photon_ml_tpu.cli import serve_driver
    from photon_ml_tpu.io.model_io import read_scores

    out = os.path.join(glmix["sub"], "serve")
    tel = os.path.join(glmix["sub"], "serve-telemetry")
    argv = ["--requests-avro", os.path.join(work, "val"),
            "--model-input-dir", glmix["best_dir"], "--output-dir", out,
            "--request-rows", str(sizes.serve_request_rows),
            "--num-requests", str(sizes.serve_requests),
            "--telemetry-dir", tel]
    for s in glmix["shards"]:
        argv += ["--feature-shard-configurations", s]
    t0 = time.perf_counter()
    summary = serve_driver.main(argv)
    c.note("serve: wall seconds", round(time.perf_counter() - t0, 1))
    with open(os.path.join(out, "serving-summary.json")) as f:
        on_disk = json.load(f)
    rec.runtime("serve", on_disk, {"requests": "avro-native"})
    rec.journal("serve", tel)
    c.note("serve: scores/sec batched, unbatched; p50, p95 ms (host clock)", [
        round(summary["scores_per_sec"], 1),
        round(summary["scores_per_sec_unbatched"], 1),
        summary["latency_ms_p50"], summary["latency_ms_p95"]])
    n = sizes.serve_requests * sizes.serve_request_rows
    c.check("serve: every request answered",
            summary["num_requests"] == sizes.serve_requests
            and summary["num_rows"] == n, f"{summary['num_requests']} requests, "
            f"{summary['num_rows']} rows")
    c.check("serve: no compile inside the replay",
            summary["replay_compiles"] == 0,
            f"warm {summary['warm_compiles']}, replay {summary['replay_compiles']}")
    served = np.full(n, np.nan)
    for r in read_scores(os.path.join(out, "scores")):
        served[int(r["uid"])] = r["predictionScore"]
    diff = np.abs(served - glmix["batch_scores"][:n])
    c.check("serve: served scores == batch scores on the same rows",
            np.isfinite(served).all() and float(diff.max()) <= 1e-5,
            f"max |served - batch| = {float(diff.max()):.2e} "
            f"({'bitwise' if (diff == 0).all() else 'not bitwise'})")


def leg_glm(c: Checks, rec: DriverRecord, sizes: Sizes, data: dict, work: str,
            on_tpu: bool) -> None:
    from photon_ml_tpu.cli import glm_driver

    train, val = data["train"], data["val"]

    def objective(w: np.ndarray, lam: float) -> float:
        m = (train["vals"] * w[train["cols"]]).sum(1) + w[-1]
        return float(np.sum(np.logaddexp(0, m) - train["y"] * m)
                     + 0.5 * lam * w @ w)

    def run(name, lambdas, extra):
        out, tel = os.path.join(work, name), os.path.join(work, name + "-telemetry")
        rec.mark_kernel_traces()
        t0 = time.perf_counter()
        result = glm_driver.main([
            "--input-data-path", os.path.join(work, "train"),
            "--validation-data-path", os.path.join(work, "val"),
            "--output-dir", out, "--task-type", "LOGISTIC_REGRESSION",
            "--input-format", "libsvm", "--optimizer", "LBFGS",
            "--max-iterations", "30",
            "--regularization-weights", ",".join(map(str, lambdas)),
            "--telemetry-dir", tel, "--max-restarts", "0", *extra])
        c.note(f"{name}: wall seconds", round(time.perf_counter() - t0, 1))
        with open(result.summary_path) as f:
            on_disk = json.load(f)
        rec.runtime(name, on_disk,
                    {"train": "libsvm-native", "validation": "libsvm-native"})
        _, snap = rec.journal(name, tel)
        # index map order is the driver's business: place by feature name
        weights = {}
        for lam in lambdas:
            w = np.zeros(sizes.glm_d + 1)
            with open(os.path.join(out, "models-text", f"{float(lam)}.txt")) as f:
                for line in f:
                    key, _, value = line.rstrip("\n").rpartition("\t")
                    name_ = key.split("\t")[0]
                    j = sizes.glm_d if name_ == "(INTERCEPT)" else int(name_)
                    w[j] = float(value)
            weights[lam] = w
        return on_disk, weights, rec.kernel_traces(snap)

    single, w1, traces = run("glm-single", [1.0], [])
    if on_tpu:
        c.check("glm-single: the solve holds the compiled kernel",
                traces["compiled"] >= 1 and traces["interpreted"] == 0, traces)
    else:
        c.note("glm-single: kernel traces (not judged here)", traces)
    grid, wg, traces = run("glm-grid", [0.1, 1.0, 10.0, 100.0], ["--grid-parallel"])
    c.check("glm-grid: vmapped lanes stay on the XLA path",
            traces["compiled"] == 0 and traces["interpreted"] == 0, traces)
    oracle = auc(val["oracle_margin"], val["y"])
    floor = 0.5 + sizes.auc_lift_floor * (oracle - 0.5)
    for name, summ in (("glm-single", single), ("glm-grid", grid)):
        best = summ["validation_metrics"][str(summ["best_lambda"])]["AUC"]
        c.check(f"{name}: validation AUC clears the floor", best >= floor,
                f"{best:.4f} vs floor {floor:.4f} (oracle {oracle:.4f})")
    for lam, w in list(w1.items()) + list(wg.items()):
        c.check(f"glm lambda={lam}: coefficients finite",
                bool(np.isfinite(w).all() and np.abs(w).max() > 0), "")
    # the kernel-path and XLA-path solves of the same problem reach the same
    # objective (numpy f64 at the saved coefficients)
    f_kernel, f_xla = objective(w1[1.0], 1.0), objective(wg[1.0], 1.0)
    c.check("glm: kernel-path and vmapped-path solves agree (lambda=1 objective)",
            abs(f_kernel - f_xla) <= 1e-4 * abs(f_xla),
            f"{f_kernel:.4f} vs {f_xla:.4f}, rel "
            f"{abs(f_kernel - f_xla) / abs(f_xla):.2e}")
    norms = [float(np.linalg.norm(wg[lam][:-1])) for lam in sorted(wg)]
    c.check("glm-grid: heavier lambda, smaller ||w||",
            all(b < a for a, b in zip(norms, norms[1:])),
            [round(x, 4) for x in norms])


def compare_models(c: Checks, runs: dict) -> None:
    """Other meshes vs the first (one chip): same data, same solver, another
    layout. The solves are f32 and stop after 10 L-BFGS iterations, so the
    layouts' different summation order is amplified by the line searches:
    coefficients agree to a few 1e-3 of the largest one — measured alike on
    the v5e and on a 4-device CPU mesh in f32 (in x64 the suite pins the
    layouts equal) — while the loss they reach agrees to 1e-5. The loss is
    the judged invariant; the coefficient bound only catches a layout that
    trains a different problem."""
    base_name, base = next(iter(runs.items()))
    for name, other in list(runs.items())[1:]:
        rel = abs(other["numpy_loss"] - base["numpy_loss"]) / base["numpy_loss"]
        c.check(f"model[{name}] reaches model[{base_name}]'s training loss",
                rel < 1e-4, f"{other['numpy_loss']:.7f} vs "
                f"{base['numpy_loss']:.7f} (numpy, saved models), rel {rel:.2e}")
        worst = max(
            float(np.abs(other["model"][k] - ref).max() / np.abs(ref).max())
            for k, ref in base["model"].items())
        c.check(f"model[{name}] coefficients near model[{base_name}]'s",
                worst < 2e-2, f"max |dw| / max |w| = {worst:.2e}")
        c.note(f"scores[{name}] vs scores[{base_name}] max abs diff", float(
            np.abs(other["batch_scores"] - base["batch_scores"]).max()))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU backend, kernel interpreted; "
                        "debugs this script and can never read as a pass")
    p.add_argument("--legs", default="kernel,glmix,serve,glm",
                   help="comma-separated subset (a partial run never prints ok)")
    args = p.parse_args(argv)
    legs = [x for x in args.legs.split(",") if x]
    unknown = set(legs) - {"kernel", "glmix", "serve", "glm"}
    if unknown or ("serve" in legs and "glmix" not in legs):
        p.error(f"--legs: unknown {sorted(unknown)} (serve needs glmix)")

    # the cache is placed before the first compile, by the one helper; the
    # CPU rehearsal runs without one, like the CPU suite
    from photon_ml_tpu.util import compile_cache

    if args.rehearse_cpu:
        compile_cache.disable_compile_cache()
    else:
        compile_cache.configure_compile_cache()
    cache_dir = compile_cache.cache_dir_in_use()
    import jax

    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    if args.rehearse_cpu:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse-cpu needs JAX_PLATFORMS=cpu, found "
                  f"{platform!r}", file=sys.stderr)
            return EXIT_FAILED
        sizes, tag = REHEARSAL, "REHEARSAL "
    elif platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r} ({kind}), not a "
              "TPU — refusing to run. (--rehearse-cpu debugs the script at "
              "tiny size on CPU; it is not a pass.)", file=sys.stderr)
        return EXIT_NO_CHIP
    else:
        sizes, tag = FULL, ""
    on_tpu = platform == "tpu"

    import jaxlib

    from photon_ml_tpu.telemetry.probes import CompileMonitor, runtime_stamp

    c = Checks(tag)
    stamp = runtime_stamp()
    c.note("platform", platform)
    c.note("device_kind", kind)
    c.note("device_count", count)
    c.note("versions", {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                        "libtpu": stamp["libtpu_version"]})
    c.note("compile cache directory", cache_dir)
    c.note("sizes", dataclasses.asdict(sizes))
    c.note("reduced", [] if sizes == FULL else
           ["rehearsal: every row/entity count cut; widths kept"])

    def cache_files() -> int:
        return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(
            cache_dir) else 0

    cache_files_before = cache_files()
    # the backend-compile event fires on persistent-cache hits too (it wraps
    # the lookup), so hits are counted from the cache's own events
    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0}

    def on_event(name: str, **_) -> None:
        key = name.rsplit("/", 1)[-1]
        if name.startswith("/jax/compilation_cache/") and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    # a rehearsal (the test suite runs one) never wipes a chip run's files
    work_root = os.path.join(WORK_DIR, "rehearsal" if args.rehearse_cpu else "run")
    shutil.rmtree(work_root, ignore_errors=True)
    os.makedirs(work_root)
    rec = DriverRecord(c, platform)
    t_start = time.perf_counter()
    with CompileMonitor() as compiles:
        if "kernel" in legs:
            leg_kernel(c, sizes, on_tpu)
        if "glmix" in legs:
            work = os.path.join(work_root, "glmix")
            t0 = time.perf_counter()
            data = make_glmix(sizes, args.seed, work)
            c.note("glmix: data generation seconds",
                   round(time.perf_counter() - t0, 1))
            # 4 chips: the same train+score per mesh, models compared
            meshes = ([None] if count == 1 else
                      ["data=1,model=1", f"data={count},model=1"]
                      + ([f"data={count // 2},model=2"] if count % 2 == 0 else []))
            runs = {}
            for mesh in meshes:
                runs[mesh or "distributed"] = leg_glmix(
                    c, rec, sizes, data, work, mesh, on_tpu)
            if len(runs) > 1:
                compare_models(c, runs)
            if "serve" in legs:
                leg_serve(c, rec, sizes, work, next(iter(runs.values())))
        if "glm" in legs:
            work = os.path.join(work_root, "glm")
            leg_glm(c, rec, sizes, make_libsvm(sizes, args.seed, work), work,
                    on_tpu)
    wall = time.perf_counter() - t_start
    c.note("wall seconds", round(wall, 1))
    c.note("backend compiles", {"count": compiles.count,
                                "seconds": round(compiles.seconds, 1)})
    c.note("compile cache files (before, after)",
           [cache_files_before, cache_files()])
    c.note("persistent cache (requests, hits)",
           [cache_events["compile_requests_use_cache"], cache_events["cache_hits"]])
    memory = runtime_stamp()["device_memory"]
    c.note("peak_bytes_in_use per device",
           {m["id"]: m["peak_bytes_in_use"] for m in memory})
    if on_tpu:
        c.check("every device reports non-trivial peak_bytes_in_use",
                all((m["peak_bytes_in_use"] or 0) > 1 << 20 for m in memory)
                or "glmix" not in legs, "")

    complete = set(legs) == {"kernel", "glmix", "serve", "glm"}
    os.makedirs(REPORT_DIR, exist_ok=True)
    name = ("chip_smoke-rehearsal.json" if args.rehearse_cpu
            else f"chip_smoke-{count}chip.json")
    with open(os.path.join(REPORT_DIR, name), "w") as f:
        json.dump({"failed": c.failed, "legs": legs, "seed": args.seed,
                   **c.report}, f, indent=1, default=str)
    device = {"platform": platform, "kind": kind, "count": count}
    if c.failed:
        print(f"{tag}chip_smoke: {len(c.failed)} check(s) FAILED:", file=sys.stderr)
        for name in c.failed:
            print(f"  - {name}", file=sys.stderr)
        print(json.dumps({"ok": False, "failed": c.failed, "device": device}))
        return EXIT_FAILED
    if args.rehearse_cpu or not complete:
        # never an "ok": a rehearsal or a partial run is not the chip check
        print(json.dumps({
            "rehearsal" if args.rehearse_cpu else "partial": True,
            "legs_passed": legs, "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
