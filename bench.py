"""Benchmark: λ-grid GLM training + fused GAME sweep + hot-loop bandwidth.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"extra_metrics": [...]}. The primary metric is the vmapped λ-grid workload;
extra_metrics carry the flagship fused GAME sweep (SURVEY.md §3.1 call
stack) and the hot-loop HBM-bandwidth figures (autodiff/XLA vs the Pallas
kernel, vs the 819 GB/s v5e roofline).

Primary workload: the reference's hot loop (SURVEY.md §3.4) folded over a
32-point regularization grid — the λ-grid expansion of GameTrainingDriver
(:612-621) that the Spark reference trains sequentially, one L-BFGS run per
λ. Here the whole grid trains *simultaneously* (photon_ml_tpu
train_glm_grid): vmapped L-BFGS lanes share every read of the [n, d]
feature block, so per-lane margins become one X @ W matmul on the MXU, and
measured wall-clock is nearly flat in the number of lanes (extra λs are
almost free). ``vs_baseline`` is the ratio of example-iteration throughput
(examples x L-BFGS iterations per second) against scipy's Fortran L-BFGS-B
solving the same grid sequentially on the host CPU — iteration-normalized
because the two solvers terminate after different iteration counts
(stand-in for the reference's single-executor Breeze/JVM path; the
reference publishes no benchmark numbers, see BASELINE.md).

Measurement notes:
- Every timing ends on a host read of a result (the same wait as
  ``block_until_ready``).
- The grid metric includes the fixed per-call cost (dispatch, launch, the
  host read), while the bandwidth/sweep figures are *marginal* (K-step
  differencing cancels the fixed cost).
- Each rep perturbs warm starts / initial state from a fresh PRNG seed so
  no two executions are identical.
- No row of this file has been measured on this round's code (PERF.md);
  ROADMAP S1 replaces it with cells that refuse to run without a chip.
- The CPU baseline runs on an n/8 subsample; both sides are expressed as
  example-iterations/sec, which is size-invariant (per-iteration cost is
  linear in n at fixed d).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# the measurement discipline (median-of-K, K_hi/K_lo differencing, stream
# calibration) lives in the telemetry library since r6 — bench.py is one
# consumer; probes imports no jax at module load, so the platform choice
# below still happens first
from photon_ml_tpu.telemetry.probes import (
    GATE_REPS,  # median-of-K for every gate metric (single-shot host-clock
                # numbers spread; VERDICT r3 #8)
    MarginalTimer,
    median_spread,
    read_scalar,
    scan_step_marginal,
    stream_calibration,
)

N, D, MAX_ITER, GRID = 1 << 18, 512, 30, 32
CPU_SUBSAMPLE = 1 << 15
HBM_ROOFLINE_GBPS = 819.0  # v5e

#: the driver's artifact capture tails the last 2,000 bytes of stdout; the
#: ONE JSON line must fit or the official record loses the primary metric
#: (BENCH_r04/r05 both captured `parsed: null` from over-long unit prose).
#: Methodology prose lives in BASELINE.md + this module's docstrings; units
#: stay telegraphic. tests/test_bench_line.py pins the budget via
#: sample_report().
MAX_LINE_BYTES = 2000


# -- compact report rows (shared by the live bench and sample_report) --------


def _num(v: float):
    """Compact row number: one decimal below 1000, integer above (a 6e8
    rate's sub-unit digits are noise; the line budget is the constraint)."""
    return round(float(v), 1) if abs(v) < 1000 else int(round(float(v)))


def _row(metric: str, value: float, spread, unit: str) -> dict:
    return {"metric": metric, "value": _num(value),
            "spread": [_num(s) for s in spread], "unit": unit}


def render_report(report: dict) -> str:
    """The ONE stdout line: compact separators (no space after ,/:) — the
    driver tail-parses it as JSON either way, and the ~130 bytes of
    separator whitespace are better spent on metrics
    (tests/test_bench_line.py measures THIS rendering)."""
    return json.dumps(report, separators=(",", ":"))


def write_sidecar(report: dict, directory: str, *, config: dict | None = None):
    """The full UNSLIMMED report as ``<dir>/bench-report.json`` (ISSUE 12):
    never subject to the driver's 2,000-byte tail, every row's compact unit
    pre-parsed into typed fields (telemetry/bench_history.parse_unit), so
    ``dev/doctor.py`` reads structure instead of regexing the captured
    line — and prefers this file when present. The stdout contract is
    untouched: the ONE JSON line stays the driver's official record.
    Written atomically (tmp + os.replace); returns the final path."""
    import tempfile

    from photon_ml_tpu.telemetry.bench_history import (
        SIDECAR_FILENAME,
        parse_unit,
    )

    def with_parsed(row: dict) -> dict:
        return dict(row, parsed_unit=parse_unit(row["metric"], row["unit"]))

    sidecar = {
        "schema": 1,
        "kind": "bench_report",
        "config": config or {},
        "report": dict(
            with_parsed(report),
            extra_metrics=[with_parsed(r) for r in report["extra_metrics"]],
        ),
    }
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, SIDECAR_FILENAME)
    fd, staged = tempfile.mkstemp(dir=directory, prefix=".bench-report-",
                                  suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(sidecar, f, indent=2)
        os.replace(staged, path)
    except BaseException:
        if os.path.exists(staged):
            os.unlink(staged)
        raise
    return path


def _unit_primary(lane_iters: int, grid_sec: float) -> str:
    # config prose (n, d, λ-grid width, grid seconds) lives in the sidecar
    # config and BASELINE.md — the line budget spends on the lane-iteration
    # count
    del grid_sec
    return f"ex*it/s {lane_iters}it"


def _unit_stream() -> str:
    # same-run calibration probe; the row key names it, roof = v5e roofline
    return f"roof{HBM_ROOFLINE_GBPS:.0f}"


def _unit_hot_loop(note: str, frac: float) -> str:
    # the metric key already names the variant (the HOT_LOOP_NOTES prose
    # lives in BASELINE.md); ms/eval is derivable from GB/s over [n, d],
    # and the cal fraction from the same-run stream-probe row (the
    # documented calibration_fraction fallback) — budget-trimmed
    del note, frac
    return "GB/s"


def _unit_sweep(newton: bool) -> str:
    # the metric key names the variant — budget-trimmed
    del newton
    return "ms/sw"


def _unit_sweep_scheduled() -> str:
    # compare against fused_game_sweep_ms from the SAME run only (the
    # calibration discipline); includes the scheduler's host reads
    return "ms/sw"


def _unit_sweep_composed(ell_ms: float, cov: float) -> str:
    # compare against the embedded same-run ELL+unscheduled sweep only
    # (the calibration discipline); one Zipfian dataset, two configs —
    # cov rides the same-run hybrid row
    del cov
    return f"ms/sw ELLunsr {ell_ms:.0f}"


def _unit_sparse_1e7(ms_per_iter: float) -> str:
    del ms_per_iter  # derivable from the row value; budget-trimmed
    return "nnz*it/s d=1e7"


def _unit_sparse_hybrid(ell_ms: float, cov: float, k_hot: int) -> str:
    # compare against the embedded same-run ELL ms/it only (the calibration
    # discipline): same Zipfian data, same process, fractional comparison;
    # k_hot is fixed config (sidecar/BASELINE.md) — budget-trimmed
    del k_hot
    return f"ms/it cov{cov:.2f} ELLsr {ell_ms:.0f}"


def _unit_sparse_1e8(entry_iters_m: float) -> str:
    del entry_iters_m  # derivable from the row value; budget-trimmed
    # the metric key names d=1e8; hot512 is fixed config (BASELINE.md)
    return "ms/TRON-it"


def _unit_stream_game(visits_d: int, visits_u: int, sweeps_d: int,
                      sweeps_u: int, off_ms: float) -> str:
    # compare DuHL vs uniform from the SAME run only (the calibration
    # discipline): v = RE chunk visits to tolerance (ordered/uniform),
    # sw = sweeps to tolerance, OFF = same-run prefetch-OFF ms/sweep
    return (
        f"ms/sw v{visits_d}/{visits_u} "
        f"sw{sweeps_d}/{sweeps_u} OFF{off_ms:.0f}"
    )


def _unit_stream_game_ranks(rank_mb: float, input_mb: float,
                            one_rank_ms: float) -> str:
    # compare against the embedded same-run single-rank sweep ms only (the
    # calibration discipline); rb = max per-rank decoded bytes / global
    # input bytes — the partitioned-read evidence (each rank must decode
    # STRICTLY less than the whole input; wall-clock on virtual ranks is
    # thread-serialized and never the win criterion)
    return f"ms/sw rb{rank_mb:.2f}/{input_mb:.2f}MB 1rk{one_rank_ms:.0f}"


def _unit_refresh(lanes_solved: int, lanes_total: int, full_ms: float) -> str:
    # compare against the embedded same-run full-retrain ms only (the
    # calibration discipline); ln = RE lane-solves refresh/full — the
    # selection evidence (refresh must be STRICTLY fewer)
    return f"ms/rf ln{lanes_solved}/{lanes_total} fullsr {full_ms:.0f}"


def _unit_serve(p95_ms: float, unbatched_rate: float) -> str:
    # compare against the embedded same-run one-request-per-dispatch rate
    # only (the calibration discipline); p95 = request latency inside the
    # micro-batching loop at this replay's closed-loop arrival rate
    return f"sc/s p95 {p95_ms:.0f}ms 1/dsp sr {unbatched_rate:.0f}"


def _unit_search(seq_rate: float) -> str:
    # compare against the embedded same-run one-config-per-solve rate only
    # (the calibration discipline); seq = sequential configs/sec through
    # the SAME driver with lane_budget=1 — vmapped lanes are the only
    # lever; rounds/lane_budget are fixed config (sidecar/BASELINE.md)
    return f"cfg/s seq{seq_rate:.1f}"


def _unit_stream_chunked(off_ms: float, overlap: float, chunks: int) -> str:
    # compare against the embedded same-run prefetch-OFF ms/epoch only
    # (the calibration discipline); zdec = per-chunk zlib-inflate decode
    # stand-in; ovl = epoch overlap fraction (decode hidden behind compute)
    return f"ms/ep {chunks}ch OFF{off_ms:.0f} ovl{overlap:.2f}"


#: hot-loop row labels -> telegraphic GB/s notes (prose: BASELINE.md r4)
HOT_LOOP_NOTES = {
    "autodiff_xla": "2Xpass",
    "pallas_kernel": "1pass",
    "pallas_bf16": "bf16acc",
    "pallas_shardmap_mesh1": "shmap",
}


def sample_report() -> dict:
    """The report with worst-case-width representative values, through the
    SAME row/unit builders main() uses — what tests/test_bench_line.py
    measures against MAX_LINE_BYTES without touching a TPU.

    Widths are per metric CLASS, each comfortably above anything a sane
    run can produce (r1-r5 actuals: λ-grid rate ~6e8, GB/s ~750, sweeps
    18-50 ms, iters ≤ 750 ms, streamed epochs/sweeps ~1-3 s; main() still
    hard-raises if a pathological line exceeds the budget): training rate
    rows 1e9, bandwidth rows 1e4 GB/s (12x the roofline), per-iteration/
    sweep ms rows 1e4 (10+ s where actuals are sub-second), epoch-scale
    streaming ms rows 1e4 (10 s/epoch vs ~3 s worst observed), serving
    rows 1e6 sc/s / 1e4 ms p95 / 1e5 unbatched sc/s (decades above any
    recorded rate), refresh lane pairs 3 digits (the
    bench fixture has 256 entities), partitioned-read MB pairs 99.99 (the
    ranks fixture is a fixed ~0.2 MB synthetic — byte counts are
    deterministic, not chip-lottery-scaled), search rows 1e4 cfg/s with a
    1e4-cfg/s embedded sequential rate (tournaments run tens of configs
    per second at best). The r20 line-budget trims: fixed-config fields
    (k_hot, d, λ-grid width) and the hot-loop cal fraction moved to the
    sidecar/BASELINE.md — the doctor recomputes the fraction from the
    same-run stream-probe row (calibration_fraction's documented
    fallback)."""
    rate, rate_sp = 999999999.9, [999999999.9, 999999999.9]
    gbps, gbps_sp = 9999.9, [9999.9, 9999.9]
    ms, ms_sp = 9999.9, [9999.9, 9999.9]
    sc, sc_sp = 999999.9, [999999.9, 999999.9]
    extra = [
        _row("fe_hot_loop_stream_gbps", gbps, gbps_sp,
             _unit_stream())
    ]
    extra += [
        _row(f"fe_hot_loop_hbm_gbps_{label}", gbps, gbps_sp,
             _unit_hot_loop(note, 9.99))
        for label, note in HOT_LOOP_NOTES.items()
    ]
    extra += [
        _row("fused_game_sweep_ms", ms, ms_sp, _unit_sweep(newton=False)),
        _row("fused_game_sweep_newton_ms", ms, ms_sp, _unit_sweep(newton=True)),
        _row("fused_game_sweep_scheduled_ms", ms, ms_sp,
             _unit_sweep_scheduled()),
        _row("sparse_giant_fe_entry_iters_per_sec", rate, rate_sp,
             _unit_sparse_1e7(9999.9)),
        _row("sparse_giant_fe_hybrid", ms, ms_sp,
             _unit_sparse_hybrid(9999.4, 9.99, 256)),
        _row("sparse_giant_fe_composed", ms, ms_sp,
             _unit_sweep_composed(9999.4, 9.99)),
        _row("sparse_1e8_fe_tron_ms_per_iter", ms, ms_sp,
             _unit_sparse_1e8(999.9)),
        _row("stream_fe_chunked", ms, ms_sp,
             _unit_stream_chunked(9999, 9.99, 99)),
        _row("stream_game_duhl", ms, ms_sp,
             _unit_stream_game(999, 999, 99, 99, 9999.4)),
        _row("stream_game_ranks", ms, ms_sp,
             _unit_stream_game_ranks(99.99, 99.99, 9999.4)),
        _row("serve_microbatch", sc, sc_sp,
             _unit_serve(9999.4, 99999.4)),
        _row("refresh_incremental", ms, ms_sp,
             _unit_refresh(999, 999, 9999.4)),
        _row("search_throughput", ms, ms_sp,
             _unit_search(9999.9)),
    ]
    report = _row(
        "glm_lambda_grid_example_iters_per_sec", rate, rate_sp,
        _unit_primary(99999, 999.999),
    )
    report["vs_baseline"] = 9999.99
    report["extra_metrics"] = extra
    return report


def _make_data(n: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(d,)).astype(np.float32) / np.sqrt(d)
    x = rng.normal(size=(n, d)).astype(np.float32)
    logits = x @ w_true
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return x, y


def _grid(k: int) -> np.ndarray:
    return np.logspace(-2, 2, k)


def bench_tpu(x, y):
    """Returns (median_grid_sec, [min, max], lane_iters) for one 32-λ grid."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

    n, d = x.shape
    batch = LabeledPointBatch.create(jax.device_put(x), jax.device_put(y))
    # use_pallas=False: the grid vmaps 32 solver lanes over one X read — a
    # Pallas call inside the vmapped while_loop would batch into a serial
    # per-lane loop (measured 40x slower; see ops/objective.py docstring)
    objective = GLMObjective(LogisticLoss(), l2_weight=0.0, use_pallas=False)

    # The same vmapped-lane program train_glm_grid compiles, inlined so the
    # bench can read per-lane iteration counts and sync on a scalar.
    @jax.jit
    def run_grid(b, l2v, seed):
        bound = objective.bind(b)

        def solve_one(l2, key):
            def vg(w):
                v, g = bound.value_and_grad(w)
                return v + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w

            w0 = 1e-4 * jax.random.normal(key, (d,), jnp.float32)
            return minimize_lbfgs(vg, w0, max_iter=MAX_ITER, tolerance=0.0)

        keys = jax.random.split(jax.random.PRNGKey(seed), l2v.shape[0])
        rs = jax.vmap(solve_one)(l2v, keys)
        return rs.iterations.sum(), rs.value.sum()

    l2v = jnp.asarray(_grid(GRID), jnp.float32)
    float(run_grid(batch, l2v, 0)[1])  # compile + sync

    def timed(k, seed0):
        # k pipelined grid solves (fresh PRNG warm starts), one final host
        # read: per-call dispatch overlaps device execution, so k-vs-1
        # differencing isolates the device time of one full grid
        t0 = time.perf_counter()
        results = [run_grid(batch, l2v, seed0 + i) for i in range(k)]
        for _, checksum in results:
            float(checksum)  # host read: hard sync
        elapsed = time.perf_counter() - t0
        return elapsed, sum(int(it) for it, _ in results)

    state = {"iters": 0, "seed": [0]}

    def once():
        s0 = state["seed"][0]
        state["seed"][0] += 100
        lo = min(timed(1, s0 + s)[0] for s in (1, 2))
        hi_t, hi_iters = min(
            (timed(3, s0 + s) for s in (10, 20)), key=lambda r: r[0]
        )
        state["iters"] = hi_iters // 3
        return max((hi_t - lo) / 2, 1e-6)

    marginal, spread = median_spread(once)
    return marginal, spread, state["iters"]


def bench_hot_loop_bandwidth(x, y) -> list[dict]:
    """Marginal per-eval cost of the FE value+gradient hot loop: the
    single-pass Pallas kernel (the TPU DEFAULT since r4 — f32 and bf16
    feature blocks) vs autodiff/XLA (2 X passes), as achieved HBM GB/s
    against a same-run stream calibration.

    K-step ``lax.scan`` differencing (K_hi vs K_lo evals in one jit call)
    cancels the fixed per-call cost; every figure is a
    median-of-GATE_REPS marginal with [min, max] spread.
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMObjective

    from photon_ml_tpu.data.game_data import build_game_dataset
    from photon_ml_tpu.io.data_reader import (
        FeatureShardConfiguration,
        shard_np_dtypes,
    )
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.sharded_dense import ShardedDenseGLMObjective

    n, d = x.shape
    xbytes = n * d * 4
    batch = LabeledPointBatch.create(jax.device_put(x), jax.device_put(y))
    # bf16 block via the PRODUCT path: FeatureShardConfiguration(dtype=bf16)
    # -> shard_np_dtypes -> build_game_dataset(shard_dtypes=...), the exact
    # chain `--feature-shard-configurations ...,dtype=bf16` drives — so
    # this row measures what the CLI actually feeds the hot loop
    # (VERDICT r4 #3)
    _ds = build_game_dataset(
        labels=y, feature_shards={"global": x},
        shard_dtypes=shard_np_dtypes(
            {"global": FeatureShardConfiguration(("f",), dtype="bfloat16")}
        ),
    )
    batch_bf16 = LabeledPointBatch.create(
        _ds.feature_shards["global"], jax.device_put(y)
    )
    assert batch_bf16.features.dtype == jnp.bfloat16
    del _ds
    # wide K spread: the K_hi-K_lo device-time delta must dwarf the
    # call-to-call jitter of the fixed per-call cost, or a marginal can
    # come out NEGATIVE
    k_lo, k_hi = 16, 256
    rng = np.random.default_rng(7)

    def marginal_of(step_fn, b):
        return scan_step_marginal(
            step_fn, b, d, k_lo=k_lo, k_hi=k_hi, reps=GATE_REPS, rng=rng
        )

    # Same-run stream calibration (one X read per step), so hot-loop rates
    # can be stated as fractions of what this process streamed. The probe
    # is an XLA matvec, not a bandwidth ceiling: fractions >1.0 can be real.
    cal = stream_calibration(
        batch.features, k_lo=k_lo, k_hi=k_hi, reps=GATE_REPS, rng=rng
    )
    stream_gbps = cal["gbps"]
    out = [_row(
        "fe_hot_loop_stream_gbps",
        round(stream_gbps, 1),
        [round(s, 1) for s in cal["spread_gbps"]],
        _unit_stream(),
    )]
    # prose for each row lives in HOT_LOOP_NOTES + BASELINE.md (the r4
    # kernel study); bf16 rides the reader's dtype=bf16 product cast so
    # this measures what the CLI actually feeds the hot loop (VERDICT r4
    # #3); mesh1 = the same kernel inside shard_map (parallel/
    # sharded_dense.py, the multi-chip path — parity means the wrapper is
    # free, VERDICT r4 #1)
    for label, obj, b, nbytes in (
        ("autodiff_xla",
         GLMObjective(LogisticLoss(), l2_weight=0.5, use_pallas=False),
         batch, xbytes),
        ("pallas_kernel",
         GLMObjective(LogisticLoss(), l2_weight=0.5, use_pallas=True),
         batch, xbytes),
        ("pallas_bf16",
         GLMObjective(LogisticLoss(), l2_weight=0.5, use_pallas=True),
         batch_bf16, xbytes // 2),
        ("pallas_shardmap_mesh1",
         ShardedDenseGLMObjective(LogisticLoss(), make_mesh(data=1, model=1),
                                  l2_weight=0.5, use_pallas=True),
         batch, xbytes),
    ):
        def step(w, bb, _obj=obj):
            v, g = _obj.value_and_gradient(w, bb)
            return w - 1e-4 * g, v

        m, sp = marginal_of(step, b)
        out.append(_row(
            f"fe_hot_loop_hbm_gbps_{label}",
            round(nbytes / m / 1e9, 1),
            [round(nbytes / s / 1e9, 1) for s in sp[::-1]],
            _unit_hot_loop(
                HOT_LOOP_NOTES[label],
                xbytes / m / 1e9 / stream_gbps,
            ),
        ))
    return out


def bench_game_sweep() -> list[dict]:
    """The flagship workload (SURVEY §3.1): one fused GAME CD sweep — FE +
    2 RE coordinates + rescoring — as marginal ms/sweep (sweep-count
    differencing cancels dispatch + input-layout fixed costs).

    Two rows: the historical metric (10 LBFGS iters/coordinate, unchanged
    definition since r1) and the same sweep with the RE coordinates on the
    r5 batched-Newton solver (optim/newton.py). The r5 decomposition
    (not re-measured on this round's code) attributed ~87% of the sweep to
    the two vmapped RE LBFGS solves (~2 ms per coordinate-iteration,
    op-count-bound at ~40x the bucket's streaming cost); Newton does the
    same per-entity convergence in ~4 fused ops per iteration and
    converges small-d GLMs quadratically."""
    import jax

    from photon_ml_tpu.data.game_data import (
        build_game_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
        GameTrainState,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d_fe, d_re = 1 << 17, 256, 16
    n_users, n_items = 2000, 1500
    users = np.array([f"u{i}" for i in rng.integers(0, n_users, size=n)])
    items = np.array([f"i{i}" for i in rng.integers(0, n_items, size=n)])
    x_fe = rng.normal(size=(n, d_fe)).astype(np.float32)
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    y = (x_fe @ rng.normal(size=d_fe).astype(np.float32) / np.sqrt(d_fe)
         + rng.normal(size=n).astype(np.float32))
    dataset = build_game_dataset(
        labels=y,
        feature_shards={"global": x_fe, "per_entity": x_re},
        entity_keys={"user": users, "item": items},
        dtype=np.float32,
    )
    re_datasets = {
        t: build_random_effect_dataset(dataset, t, "per_entity",
                                       bucket_sizes=(128,))
        for t in ("user", "item")
    }
    from photon_ml_tpu.optim.optimizer import LaneSchedulerConfig

    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=10)
    newton = OptimizerConfig(optimizer_type=OptimizerType.NEWTON,
                             max_iterations=10)
    # probe/rescue lane scheduling (algorithm/lane_scheduler.py) + the live
    # function-decrease stop: the same 10-iteration LBFGS budget, but lanes
    # that converge in the 2-iteration probe never pay the rest. Compare
    # against fused_game_sweep_ms from the SAME run per the calibration
    # discipline — the scheduled step's host reads ride the marginal.
    scheduled = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=10,
        rel_function_tolerance=1e-6,
        scheduler=LaneSchedulerConfig(probe_iterations=2),
    )

    def make_program(re_opt):
        return GameTrainProgram(
            TaskType.LINEAR_REGRESSION,
            FixedEffectStepSpec(feature_shard_id="global", optimizer=opt,
                                l2_weight=1.0),
            (
                RandomEffectStepSpec("user", "per_entity", re_opt, l2_weight=1.0),
                RandomEffectStepSpec("item", "per_entity", re_opt, l2_weight=1.0),
            ),
            use_pallas_fe=True,  # single chip: the FE solve takes the kernel
        )

    def measure(program, step_fn=None):
        return _sweep_marginal(program, dataset, re_datasets,
                               step_fn=step_fn)

    per_sweep, sp = measure(make_program(opt))
    newton_sweep, newton_sp = measure(make_program(newton))

    sched_program = make_program(scheduled)
    from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler

    schedulers = {
        s.re_type: LaneScheduler(s.optimizer.scheduler)
        for s in sched_program.re_specs
    }

    def sched_step(data, buckets, state):
        return sched_program.step_scheduled(
            data, buckets, state, schedulers=schedulers
        )

    sched_sweep, sched_sp = measure(sched_program, step_fn=sched_step)
    return [
        _row(
            "fused_game_sweep_ms",
            round(per_sweep * 1e3, 1),
            [round(s * 1e3, 1) for s in sp],
            _unit_sweep(newton=False),
        ),
        _row(
            "fused_game_sweep_newton_ms",
            round(newton_sweep * 1e3, 1),
            [round(s * 1e3, 1) for s in newton_sp],
            _unit_sweep(newton=True),
        ),
        _row(
            "fused_game_sweep_scheduled_ms",
            round(sched_sweep * 1e3, 1),
            [round(s * 1e3, 1) for s in sched_sp],
            _unit_sweep_scheduled(),
        ),
    ]


def _sweep_marginal(program, dataset, re_datasets, step_fn=None):
    """Marginal seconds per fused GAME sweep (K-sweep differencing, fresh
    perturbed warm starts per rep — the fused-sweep discipline shared by
    bench_game_sweep and bench_game_sweep_composed). Returns (median,
    spread) like MarginalTimer."""
    import jax

    from photon_ml_tpu.parallel.distributed import GameTrainState

    step = step_fn if step_fn is not None else program.step
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    base_state = program.init_state(dataset, re_datasets, None)

    def perturbed(seed):
        # fresh warm start per rep: identical repeat executions can be
        # served from a backend cache (see module docstring)
        key = jax.random.PRNGKey(seed)
        keys = jax.random.split(key, 1 + len(base_state.re_tables))
        return GameTrainState(
            fe_coefficients=base_state.fe_coefficients
            + 1e-3 * jax.random.normal(keys[0], base_state.fe_coefficients.shape),
            re_tables={
                t: tab + 1e-3 * jax.random.normal(k, tab.shape)
                for k, (t, tab) in zip(keys[1:], base_state.re_tables.items())
            },
            mf_rows=dict(base_state.mf_rows),
            mf_cols=dict(base_state.mf_cols),
        )

    def timed(k, seed):
        # k dispatches enqueue asynchronously (no host read between
        # sweeps), so per-call dispatch overlaps device execution and
        # the K-step differencing isolates true per-sweep device time
        state = perturbed(seed)
        t0 = time.perf_counter()
        for _ in range(k):
            state, loss = step(data, buckets, state)
        read_scalar(state.fe_coefficients)  # host read: hard sync
        return time.perf_counter() - t0

    timed(1, 0)  # compile + sync
    seed = [0]

    def timed_k(k):
        # two fresh-seed attempts per K, keep the best (dispatch noise)
        s0 = seed[0]
        seed[0] += 5
        return min(timed(k, s0 + s) for s in (1, 2))

    result = MarginalTimer(k_lo=1, k_hi=5, reps=GATE_REPS).measure(timed_k)
    return result.median, result.spread


def bench_game_sweep_composed() -> dict:
    """The composed configuration's device cost (ISSUE 6): ONE Zipfian
    sparse-FE GAME dataset, two configurations of the same fused sweep
    measured back to back in THIS process — (a) ELL layout + unscheduled
    RE solves (the r5-era shape) embedded in the unit, (b) hybrid hot-256
    head + probe2/rescue-scheduled RE solves, the row value. Fractional
    same-run comparison per the calibration discipline.

    The multi-host seams (partitioned ingest, SPMD rescue blocks) are
    host-side and pinned on the CPU mesh (tests/test_composed_path.py);
    what this row prices is the composed DEVICE path: hybrid margins/
    gradients inside the fused FE solve + scheduler-driven probe/rescue
    blocks for the vmapped RE solves, composing the r6 layout win with
    the r8 scheduling win on one workload."""
    import dataclasses as _dc

    from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler
    from photon_ml_tpu.data.game_data import (
        build_game_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.data.sparse_batch import HybridPolicy, SparseShard
    from photon_ml_tpu.optim.optimizer import (
        LaneSchedulerConfig,
        OptimizerConfig,
        OptimizerType,
    )
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.telemetry import default_registry
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(13)
    n, d, per_row, k_hot, d_re = 1 << 16, 1_000_000, 16, 256, 16
    rows = np.repeat(np.arange(n), per_row)
    cols = _zipf_cols(rng, n * per_row, d)
    vals = (rng.normal(size=n * per_row) / np.sqrt(per_row)).astype(np.float32)
    y = vals.reshape(n, per_row).sum(axis=1) + 0.1 * rng.normal(
        size=n
    ).astype(np.float32)
    users = np.array([f"u{i}" for i in rng.integers(0, 2000, size=n)])
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    shard = SparseShard(
        rows=rows.astype(np.int64), cols=cols.astype(np.int64), vals=vals,
        num_samples=n, feature_dim=d,
    )
    hyb_shard = _dc.replace(
        shard,
        hybrid_policy=HybridPolicy(hot_cols=k_hot, label="bench_composed"),
    )

    def make_dataset(fe_shard):
        return build_game_dataset(
            labels=y,
            feature_shards={"global": fe_shard, "per_entity": x_re},
            entity_keys={"user": users},
            dtype=np.float32,
        )

    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS,
                          max_iterations=10)
    re_sched = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=10,
        rel_function_tolerance=1e-6,
        scheduler=LaneSchedulerConfig(probe_iterations=2),
    )

    def make_program(re_opt):
        return GameTrainProgram(
            TaskType.LINEAR_REGRESSION,
            FixedEffectStepSpec(feature_shard_id="global", optimizer=opt,
                                l2_weight=1.0),
            (RandomEffectStepSpec("user", "per_entity", re_opt,
                                  l2_weight=1.0),),
        )

    ell_dataset = make_dataset(shard)
    ell_res = build_random_effect_dataset(ell_dataset, "user", "per_entity",
                                          bucket_sizes=(128,))
    ell_sweep, _ = _sweep_marginal(make_program(opt), ell_dataset,
                                   {"user": ell_res})

    hyb_dataset = make_dataset(hyb_shard)
    hyb_res = build_random_effect_dataset(hyb_dataset, "user", "per_entity",
                                          bucket_sizes=(128,))
    program = make_program(re_sched)
    schedulers = {
        s.re_type: LaneScheduler(s.optimizer.scheduler)
        for s in program.re_specs if s.optimizer.scheduler is not None
    }

    def sched_step(data, buckets, state):
        return program.step_scheduled(data, buckets, state,
                                      schedulers=schedulers)

    composed, sp = _sweep_marginal(program, hyb_dataset, {"user": hyb_res},
                                   step_fn=sched_step)
    cov = (default_registry().gauge("layout/bench_composed/hot_coverage")
           .value or 0.0)
    return _row(
        "sparse_giant_fe_composed",
        round(composed * 1e3, 1),
        [round(s * 1e3, 1) for s in sp],
        _unit_sweep_composed(ell_sweep * 1e3, cov),
    )


def _lbfgs_iter_marginal(obj, batch, d: int, k_lo: int = 4, k_hi: int = 16):
    """Median-of-GATE_REPS marginal seconds per extra L-BFGS iteration over
    one sparse batch (fresh-PRNG warm starts, k_hi-vs-k_lo differencing —
    the sparse-row discipline since r3). The batch rides as a jit ARGUMENT:
    closing over it would bake the entry arrays into the program as
    constants (a program the size of the data, recompiled per batch)."""
    import jax
    import jax.numpy as jnp

    from functools import partial

    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

    @partial(jax.jit, static_argnums=(2,))
    def run(w0, b, iters):
        r = minimize_lbfgs(obj.bind(b).value_and_grad, w0, max_iter=iters,
                           tolerance=0.0)
        return r.value + r.coefficients[0]

    def timed(iters, seed):
        key = jax.random.PRNGKey(seed)
        w0 = 1e-3 * jax.random.normal(key, (d,), jnp.float32)
        float(run(w0, batch, iters))  # compile + sync
        best = None
        for s in range(2):
            w0 = 1e-3 * jax.random.normal(jax.random.PRNGKey(seed + s + 1), (d,))
            t0 = time.perf_counter()
            float(run(w0.astype(jnp.float32), batch, iters))
            el = time.perf_counter() - t0
            best = el if best is None or el < best else best
        return best

    seed = [0]

    def once():
        s0 = seed[0]
        seed[0] += 1000
        return max(
            (timed(k_hi, s0) - timed(k_lo, s0 + 100)) / (k_hi - k_lo), 1e-6
        )

    return median_spread(once)


def _zipf_cols(rng, size: int, d: int, gamma: float = 24.0) -> np.ndarray:
    """Bounded power-law column ids (top-k nnz share (k/d)^(1/gamma)),
    scattered over [0, d) by an odd multiplicative bijection so the hot set
    is NOT contiguous — Photon's name-term bags are power-law distributed;
    this is the regime the hybrid layout exists for."""
    raw = (rng.random(size) ** gamma * d).astype(np.int64)
    return (raw * 2654435761) % d  # odd, not divisible by 5: bijective mod 10^k


def bench_sparse_fe() -> dict:
    """Giant-d sparse fixed effect on hardware: d=10⁷ logistic L-BFGS over
    flat-COO data (dense [n, d] would be n·d·4 ≈ 21 TB — the path the
    reference's 'hundreds of billions of coefficients' claim needs).
    Reported as entry-iterations/sec, marginal over extra iterations."""
    from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective

    rng = np.random.default_rng(3)
    n, d, per_row = 1 << 19, 10_000_000, 32
    rows = np.repeat(np.arange(n), per_row)
    cols = rng.integers(0, d, size=n * per_row)
    vals = rng.normal(size=n * per_row).astype(np.float32)
    support = rng.choice(d, size=256, replace=False)
    w_true = np.zeros(d, dtype=np.float32)
    w_true[support] = rng.normal(size=256).astype(np.float32)
    sig = rng.integers(0, 256, size=(n, 4))
    sig_vals = rng.normal(size=(n, 4)).astype(np.float32)
    logits = (sig_vals * w_true[support][sig]).sum(axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    rows = np.concatenate([rows, np.repeat(np.arange(n), 4)])
    cols = np.concatenate([cols, support[sig].ravel()])
    vals = np.concatenate([vals, sig_vals.ravel()])
    nnz = len(vals)
    # default ELL layout: dense row-sum margins + broadcast dz (measured
    # 330 ms/iter vs 644 flat-COO vs 733 in r2 — BASELINE.md r3 study; the
    # remaining cost is the w-gather at ~7 ns/index and the transpose
    # scatter, both per-index-rate-bound on v5e)
    batch = SparseLabeledPointBatch.from_coo(rows, cols, vals, y, dim=d,
                                             dtype=np.float32)
    obj = SparseGLMObjective(LogisticLoss(), l2_weight=0.1)
    marginal, sp = _lbfgs_iter_marginal(obj, batch, d)
    return _row(
        "sparse_giant_fe_entry_iters_per_sec",
        round(nnz / marginal, 1),
        [round(nnz / s, 1) for s in sp[::-1]],
        _unit_sparse_1e7(marginal * 1e3),
    )


def bench_sparse_fe_hybrid() -> dict:
    """Same-run hybrid-vs-ELL comparison on Zipfian-column synthetic data
    (ISSUE 5): ONE dataset, two layouts of it, both L-BFGS-iteration
    marginals measured in THIS process back to back — the fractional
    comparison the calibration discipline requires (chip-lottery pool;
    never compare absolute ms across runs).

    The hybrid view trains the 256 nnz-hottest columns (~0.6 of nonzeros
    at gamma=24) as one dense [n, 256] MXU block — ZERO per-entry index
    ops for covered entries — while the ELL tail shrinks to the cold
    residual; the expected win is index-op removal proportional to hot
    coverage (BASELINE.md r6 methodology)."""
    from photon_ml_tpu.data.sparse_batch import (
        HybridPolicy,
        SparseLabeledPointBatch,
    )
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
    from photon_ml_tpu.telemetry import default_registry

    rng = np.random.default_rng(11)
    n, d, per_row, k_hot = 1 << 19, 10_000_000, 32, 256
    rows = np.repeat(np.arange(n), per_row)
    cols = _zipf_cols(rng, n * per_row, d)
    vals = rng.normal(size=n * per_row).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    nnz = len(vals)
    common = dict(dim=d, dtype=np.float32)
    ell_batch = SparseLabeledPointBatch.from_coo(rows, cols, vals, y, **common)
    hyb_batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals, y,
        hybrid=HybridPolicy(hot_cols=k_hot, label="bench_1e7"), **common,
    )
    cov = default_registry().gauge("layout/bench_1e7/hot_coverage").value or 0.0
    obj = SparseGLMObjective(LogisticLoss(), l2_weight=0.1)
    ell_marginal, _ = _lbfgs_iter_marginal(obj, ell_batch, d)
    hyb_marginal, hyb_sp = _lbfgs_iter_marginal(obj, hyb_batch, d)
    return _row(
        "sparse_giant_fe_hybrid",
        round(hyb_marginal * 1e3, 1),
        [round(s * 1e3, 1) for s in hyb_sp],
        _unit_sparse_hybrid(ell_marginal * 1e3, cov, k_hot),
    )


def bench_sparse_fe_1e8() -> dict:
    """d=10⁸ sparse FE via TRON (VERDICT r2 #5: a step toward the
    reference's 'hundreds of billions of coefficients', README.md:77).
    TRON holds O(1) work vectors of size d where LBFGS history is 2·m·d —
    the survey's hard-parts recipe (SURVEY.md §7). Since r6 the columns are
    Zipfian (the realistic name-term regime) and the batch rides the hybrid
    layout, so TRON's CG inner loop takes the split hessian_vector: the hot
    head's forward AND transpose are dense matmuls, only the cold tail pays
    per-entry index ops (ISSUE 5 — what moves this row)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.sparse_batch import (
        HybridPolicy,
        SparseLabeledPointBatch,
    )
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
    from photon_ml_tpu.optim.tron import minimize_tron

    from functools import partial

    rng = np.random.default_rng(5)
    n, d, per_row = 1 << 18, 100_000_000, 16
    rows = np.repeat(np.arange(n), per_row)
    cols = _zipf_cols(rng, n * per_row, d)
    vals = rng.normal(size=n * per_row).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    nnz = len(vals)
    batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals, y, dim=d, dtype=np.float32,
        hybrid=HybridPolicy(hot_cols=512, label="bench_1e8"),
    )
    obj = SparseGLMObjective(LogisticLoss(), l2_weight=0.1)

    @partial(jax.jit, static_argnums=(2,))
    def run(w0, b, iters):
        bound = obj.bind(b)
        r = minimize_tron(bound.value_and_grad, bound.hessian_vector, w0,
                          max_iter=iters, max_cg_iter=2, tolerance=0.0)
        return r.value + r.coefficients[0]

    def timed(iters, seed):
        w0 = 1e-3 * jax.random.normal(jax.random.PRNGKey(seed), (d,), jnp.float32)
        float(run(w0, batch, iters))  # compile + sync
        best = None
        for s in range(2):
            w0 = 1e-3 * jax.random.normal(jax.random.PRNGKey(seed + s + 1),
                                          (d,), jnp.float32)
            t0 = time.perf_counter()
            float(run(w0, batch, iters))
            el = time.perf_counter() - t0
            best = el if best is None or el < best else best
        return best

    k_lo, k_hi = 2, 8
    seed = [0]

    def once():
        s0 = seed[0]
        seed[0] += 1000
        return max(
            (timed(k_hi, s0) - timed(k_lo, s0 + 100)) / (k_hi - k_lo), 1e-6
        )

    marginal, sp = median_spread(once)
    return _row(
        "sparse_1e8_fe_tron_ms_per_iter",
        round(marginal * 1e3, 1),
        [round(s * 1e3, 1) for s in sp],
        _unit_sparse_1e8(nnz / marginal / 1e6),
    )


def bench_stream_fe_chunked() -> dict:
    """Out-of-core chunked epoch, prefetch ON vs OFF back to back in THIS
    process (ISSUE 7). One synthetic d=512 dense dataset streams as 16
    fixed-shape chunks; every load pays a REAL host decode (zlib inflate
    of a 1/8-chunk deflate payload — the Avro block-decompress stand-in,
    scaled down to keep the bench inside the driver budget)
    before the device accumulates value+grad through the one module-level
    jit signature (chunks as ARGUMENTS, never closed over). Row value is the
    prefetch-ON ms/epoch; the same-run OFF ms/epoch and the epoch overlap
    fraction ride the unit — the win is decode hidden behind device
    compute, bounded by the decode/compute ratio, never comparable across
    runs (chip-lottery pool; BASELINE.md streaming methodology)."""
    import zlib

    import jax.numpy as jnp

    from photon_ml_tpu.algorithm.streaming import StreamingGLMObjective
    from photon_ml_tpu.io.stream_reader import ArrayChunkSource
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.telemetry import stream_counters

    n, chunk_rows = 1 << 17, 1 << 14  # 8 chunks/epoch, n >> chunk budget
    x, y = _make_data(n, D, seed=5)
    # the decode stand-in has BOTH host costs of a real Avro chunk: a
    # storage-latency wait (sleep — not CPU; this is what hides behind
    # compute even on the 1-core CPU mesh) and a CPU decompress (zlib
    # inflate of a 1/8-chunk deflate payload — scaled down for bench
    # budget, so the CPU cost class is PRESENT but smaller than a real
    # chunk's; hides only when compute runs off-host, i.e. on the TPU)
    blob = zlib.compress(x[: chunk_rows // 8].tobytes(), 1)

    def decode():
        time.sleep(0.008)
        np.frombuffer(zlib.decompress(blob), dtype=np.float32)

    source = ArrayChunkSource(x, y, chunk_rows=chunk_rows, decode_hook=decode)
    w = jnp.zeros((D,), jnp.float32)
    loss = LogisticLoss()

    def epoch_ms(prefetch: bool):
        obj = StreamingGLMObjective(
            source, loss, l2_weight=0.1, prefetch=prefetch
        )
        read_scalar(obj.value_and_grad(w)[0])  # warm the one jit signature

        def once():
            t0 = time.perf_counter()
            read_scalar(obj.value_and_grad(w)[0])
            return (time.perf_counter() - t0) * 1e3

        return median_spread(once)

    off_ms, _off_sp = epoch_ms(False)
    on_ms, on_sp = epoch_ms(True)  # overlap gauge left by the last ON epoch
    return _row(
        "stream_fe_chunked",
        round(on_ms, 1),
        [round(s, 1) for s in on_sp],
        _unit_stream_chunked(
            off_ms, stream_counters.overlap_fraction(), source.num_chunks
        ),
    )


def bench_stream_game_duhl() -> dict:
    """Streamed GAME with the DuHL importance-ordered chunk schedule vs
    uniform sweeps, back to back in THIS process (ISSUE 11). One
    gap-skewed synthetic GAME dataset (hot entities coupled to the FE
    signal, cold entities decoupled — the data shape DuHL exists for)
    streams as entity-clustered chunks with a real per-load host decode
    (sleep + zlib inflate, the Avro stand-in); both modes train to the
    SAME loss-plateau tolerance. Row value is the DuHL prefetch-ON
    ms/sweep; the unit embeds the acceptance evidence — RE chunk visits
    to tolerance ordered vs uniform (same run) and the same-run
    prefetch-OFF ms/sweep. Chunk-visit counts are deterministic; ms/sweep
    is chip-lottery-sensitive and only comparable within the run."""
    import time as _time
    import zlib

    from photon_ml_tpu.algorithm.streaming_game import (
        DuHLChunkSchedule,
        DuHLScheduleConfig,
        StreamingGameProgram,
    )
    from photon_ml_tpu.io.stream_reader import GameArrayChunkSource
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(13)
    d_fe, d_re = 32, 8
    hot_rows, cold_rows = 512, 1536
    n = hot_rows + cold_rows
    ents = np.concatenate([
        np.repeat(np.arange(4), hot_rows // 4),
        4 + np.arange(cold_rows) // 16,
    ]).astype(np.int32)
    x_fe = rng.normal(size=(n, d_fe)).astype(np.float32)
    x_fe[hot_rows:] = 0.0
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    w_fe = rng.normal(size=d_fe).astype(np.float32)
    w_re = 0.5 * rng.normal(size=(int(ents.max()) + 1, d_re))
    w_re[:4] *= 6.0
    y = (
        x_fe @ w_fe + (x_re * w_re[ents]).sum(1)
        + 0.05 * rng.normal(size=n)
    ).astype(np.float32)
    blob = zlib.compress(x_fe[:128].tobytes(), 1)

    def decode():
        _time.sleep(0.002)
        np.frombuffer(zlib.decompress(blob), dtype=np.float32)

    def source(hook=decode):
        return GameArrayChunkSource(
            features={"g": x_fe, "p": x_re}, labels=y,
            entity_idx={"user": ents}, chunk_records=128,
            cluster_by="user", decode_hook=hook,
        )

    opt = OptimizerConfig(max_iterations=4)

    def run(schedule_budget, prefetch=True, hook=decode):
        src = source(hook)
        schedule = (
            DuHLChunkSchedule(
                DuHLScheduleConfig(working_set_chunks=schedule_budget,
                                   tail_chunks_per_sweep=1),
                src.num_chunks,
            )
            if schedule_budget else None
        )
        program = StreamingGameProgram(
            TaskType.LINEAR_REGRESSION, src,
            FixedEffectStepSpec("g", opt, l2_weight=0.1),
            (RandomEffectStepSpec("user", "p", opt, l2_weight=1.0),),
            schedule=schedule, prefetch=prefetch,
        )
        t0 = time.perf_counter()
        result = program.train(num_sweeps=8, tolerance=1e-4)
        return result, (time.perf_counter() - t0) * 1e3

    run(4, hook=None)  # warm every jit signature outside the timings
    uniform, _ = run(None)
    _, off_total = run(4, prefetch=False)
    results = []

    def once():
        result, total_ms = run(4)
        results.append(result)
        return total_ms / max(result.sweeps, 1)

    on_ms, on_sp = median_spread(once)
    duhl = results[-1]
    off_ms = off_total / max(duhl.sweeps, 1)
    return _row(
        "stream_game_duhl",
        round(on_ms, 1),
        [round(s, 1) for s in on_sp],
        _unit_stream_game(
            duhl.chunk_visits, uniform.chunk_visits,
            duhl.sweeps, uniform.sweeps, off_ms,
        ),
    )


def bench_stream_game_ranks() -> dict:
    """Multi-rank partitioned streamed GAME (ISSUE 17): two virtual ranks
    (threads + InProcessExchange) agree one entity-granular chunk plan over
    the exchange, then run the composed per-rank sweep — FE partial sums
    combined in rank order, rank-local RE bucket solves, post-sweep table
    sync. Row value is the two-rank wall ms/sweep, but on virtual ranks the
    threads serialize on one host so wall-clock is NOT the win criterion:
    the unit embeds the deterministic partitioned-read evidence — max
    per-rank decoded payload bytes vs the global input bytes (rb pair;
    each rank must decode STRICTLY less than the whole input) — plus the
    same-run single-rank streamed sweep ms for scale."""
    import tempfile
    import threading

    from photon_ml_tpu.algorithm.streaming_game import StreamingGameProgram
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
    from photon_ml_tpu.io.stream_reader import (
        GameAvroChunkSource,
        plan_partitioned_game_stream,
        scan_game_stream,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.parallel.multihost import InProcessExchange
    from photon_ml_tpu.types import TaskType

    num_ranks, chunk_records, sweeps = 2, 64, 2
    rng = np.random.default_rng(29)
    n, d, n_users = 512, 8, 16
    users = np.sort(rng.integers(0, n_users, size=n))
    schema = {
        "type": "record", "name": "TrainingExampleAvro",
        "fields": [
            {"name": "label", "type": "double"},
            {"name": "userId", "type": ["string", "null"], "default": None},
            {"name": "features", "type": {"type": "array", "items": {
                "type": "record", "name": "FeatureAvro", "fields": [
                    {"name": "name", "type": "string"},
                    {"name": "term", "type": ["string", "null"],
                     "default": None},
                    {"name": "value", "type": "double"},
                ]}}},
        ],
    }
    records = []
    for i in range(n):
        x = rng.normal(size=d)
        records.append({
            "label": float(x.sum() + 0.1 * rng.normal()),
            "userId": f"u{users[i]:02d}",
            "features": [
                {"name": f"f{j}", "term": "", "value": float(x[j])}
                for j in range(d)
            ],
        })
    tmp = tempfile.mkdtemp(prefix="bench_ranks_")
    avro_io.write_container(
        os.path.join(tmp, "part-00000.avro"), schema, records,
        block_records=32,
    )
    cfg = {"global": FeatureShardConfiguration(feature_bags=("features",))}
    opt = OptimizerConfig(max_iterations=4)

    def program(source, vocabs, *, partition=None, exchange=None):
        return StreamingGameProgram(
            TaskType.LINEAR_REGRESSION, source,
            FixedEffectStepSpec("global", opt, l2_weight=0.1),
            (RandomEffectStepSpec("userId", "global", opt, l2_weight=1.0),),
            num_entities={"userId": len(vocabs["userId"])},
            exchange=exchange, partition=partition,
        )

    # same-run single-rank streamed baseline (the pre-ISSUE-17 path)
    files = avro_io.list_avro_files(tmp)
    maps, vocabs, keys, indexes, _scalars = scan_game_stream(
        files, cfg, ("userId",), cluster_by="userId"
    )

    def single_source():
        return GameAvroChunkSource(
            files, cfg, maps, chunk_records=chunk_records,
            random_effect_id_columns=("userId",), entity_vocabs=vocabs,
            cluster_by="userId", cluster_keys=keys, indexes=indexes,
        )

    program(single_source(), vocabs).train(num_sweeps=1)  # warm signatures
    t0 = time.perf_counter()
    program(single_source(), vocabs).train(num_sweeps=sweeps)
    one_rank_ms = (time.perf_counter() - t0) * 1e3 / sweeps

    partitions = [None] * num_ranks

    def rank_run(group, r):
        source, _maps, vocs, part = plan_partitioned_game_stream(
            tmp, cfg, ("userId",), exchange=group[r],
            chunk_records=chunk_records, cluster_by="userId",
        )
        partitions[r] = part
        program(source, vocs, partition=part,
                exchange=group[r]).train(num_sweeps=sweeps)

    def once():
        group = InProcessExchange.create_group(num_ranks, timeout=120.0)
        errs = [None] * num_ranks

        def work(r):
            try:
                rank_run(group, r)
            except Exception as e:
                errs[r] = e
                raise

        threads = [threading.Thread(target=work, args=(r,), daemon=True)
                   for r in range(num_ranks)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
        if any(t.is_alive() for t in threads) or any(errs):
            raise RuntimeError(f"partitioned rank failure: {errs}")
        return (time.perf_counter() - t0) * 1e3 / sweeps

    once()  # warm the partitioned signatures outside the timings
    ms, sp = median_spread(once)
    part = partitions[0]
    return _row(
        "stream_game_ranks", round(ms, 1), [round(s, 1) for s in sp],
        _unit_stream_game_ranks(
            max(part.payload_bytes) / 1e6, part.input_bytes / 1e6,
            one_rank_ms,
        ),
    )


def bench_serve_microbatch() -> dict:
    """Resident-scorer serving throughput (ISSUE 10): scores/sec through
    the micro-batching loop at the replay's p95 request latency, with the
    same-run ONE-REQUEST-PER-DISPATCH rate embedded in the unit — each
    dispatch has a fixed host cost a four-row request cannot amortize, so
    the unbatched rate is the baseline a naive online scorer would ship. One synthetic GAME model (dense FE +
    one RE table) is placed ONCE; 96 four-row requests replay closed-loop
    through shapes (128, 512); the batched rate is a median-of-GATE_REPS
    over full replays (each replay re-submits every request)."""
    from photon_ml_tpu.data.game_data import (
        build_game_dataset,
        slice_game_dataset,
    )
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import GeneralizedLinearModel
    from photon_ml_tpu.serving import MicroBatchServer, ResidentScorer
    from photon_ml_tpu.telemetry import serving_counters
    from photon_ml_tpu.types import TaskType
    import jax.numpy as jnp

    rng = np.random.default_rng(17)
    n_req, req_rows, d_fe, d_re, n_ent = 96, 4, 256, 8, 512
    n = n_req * req_rows
    users = np.array([f"u{i}" for i in rng.integers(0, n_ent, size=n)])
    dataset = build_game_dataset(
        labels=rng.normal(size=n).astype(np.float32),
        feature_shards={
            "global": rng.normal(size=(n, d_fe)).astype(np.float32),
            "per_entity": rng.normal(size=(n, d_re)).astype(np.float32),
        },
        entity_keys={"user": users},
        offsets=rng.normal(scale=0.1, size=n).astype(np.float32),
    )
    model = GameModel(models={
        "fe": FixedEffectModel(
            glm=GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    rng.normal(size=d_fe).astype(np.float32)
                )),
                TaskType.LINEAR_REGRESSION,
            ),
            feature_shard_id="global",
        ),
        "re": RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(n_ent, d_re)).astype(np.float32)
            ),
            entity_keys=dataset.entity_vocabs["user"],
            random_effect_type="user",
            feature_shard_id="per_entity",
            task=TaskType.LINEAR_REGRESSION,
        ),
    })
    requests = [
        slice_game_dataset(dataset, lo, lo + req_rows)
        for lo in range(0, n, req_rows)
    ]
    scorer = ResidentScorer(model, shapes=(128, 512))
    scorer.warm(requests[0])

    # same-run baseline: one request per dispatch, no queue
    t0 = time.perf_counter()
    for r in requests:
        scorer.score(r)
    unbatched_rate = n / max(time.perf_counter() - t0, 1e-9)

    serving_counters.reset_serving_metrics()

    def one_replay() -> float:
        with MicroBatchServer(scorer, max_wait_ms=3.0) as server:
            t0 = time.perf_counter()
            futures = [server.submit(r) for r in requests]
            for f in futures:
                f.result()
            return n / max(time.perf_counter() - t0, 1e-9)

    rate, spread = median_spread(one_replay)
    p95 = serving_counters.latency_summary()["p95"]
    return _row(
        "serve_microbatch",
        rate,
        list(spread),
        _unit_serve(p95, unbatched_rate),
    )


def bench_refresh_incremental() -> dict:
    """Incremental GAME retrain vs full retrain, back to back in THIS
    process (ISSUE 14). One synthetic GAME dataset (dense FE + one
    IDENTITY RE) trains a resident model; a few entities' labels then
    change, and the SAME updated dataset retrains both ways: the full
    warm-started fit (the honest baseline — it too starts from the
    resident model) and the incremental refresh (gradient-screened
    selection, frozen residuals, compacted selected-lane solve). Row value
    is the refresh ms (median-of-GATE_REPS); the unit embeds the
    acceptance evidence — RE lane-solves refresh/full and the same-run
    full-retrain ms. Lane counts are deterministic; ms compares within the
    run only (chip lottery)."""
    from photon_ml_tpu.algorithm.coordinates import (
        CoordinateOptimizationConfig,
    )
    from photon_ml_tpu.algorithm.refresh import RefreshPolicy
    from photon_ml_tpu.data.game_data import build_game_dataset
    from photon_ml_tpu.estimators import (
        FixedEffectCoordinateConfig,
        GameEstimator,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(23)
    n, d_fe, d_re, n_ent, n_changed = 4096, 64, 8, 256, 8
    users = np.array([f"u{i:04d}" for i in rng.integers(0, n_ent, size=n)])
    ent = np.array([int(u[1:]) for u in users])
    x_fe = rng.normal(size=(n, d_fe)).astype(np.float32)
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    w_fe = rng.normal(size=d_fe).astype(np.float32)
    w_re = rng.normal(size=(n_ent, d_re)).astype(np.float32)

    noise = 0.05 * rng.normal(size=n)

    def labels(w_tab):
        # FIXED noise: unchanged entities' rows are IDENTICAL across the
        # resident and refresh datasets, so only real change moves the
        # gradient screen
        return (
            x_fe @ w_fe + (x_re * w_tab[ent]).sum(1) + noise
        ).astype(np.float32)

    def dataset(y):
        return build_game_dataset(
            labels=y,
            feature_shards={"g": x_fe, "u": x_re},
            entity_keys={"userId": users},
        )

    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=24), l2_weight=1.0
    )
    estimator = GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fe": FixedEffectCoordinateConfig(
                feature_shard_id="g", optimization=opt
            ),
            "re": RandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard_id="u",
                optimization=opt,
            ),
        },
        num_iterations=1,
    )
    ds0 = dataset(labels(w_re))
    resident = estimator.fit(ds0).model

    w_re2 = w_re.copy()
    changed_rows = rng.choice(n_ent, size=n_changed, replace=False)
    w_re2[changed_rows] *= -2.0
    ds1 = dataset(labels(w_re2))

    policy = RefreshPolicy(gradient_tolerance=1e-1)
    # warm every jit signature (solvers + grad screen + compacted solve)
    # outside the timings — both sides below dispatch warm programs
    estimator.fit(ds1, initial_model=resident)
    estimator.refresh(ds1, resident, policy)

    # same-run full-retrain baseline: warm-started from the resident
    # model, like the refresh — the comparison isolates the selection win
    t0 = time.perf_counter()
    estimator.fit(ds1, initial_model=resident)
    full_ms = (time.perf_counter() - t0) * 1e3

    results = []

    def once() -> float:
        t0 = time.perf_counter()
        results.append(estimator.refresh(ds1, resident, policy))
        return (time.perf_counter() - t0) * 1e3

    refresh_ms, spread = median_spread(once)
    last = results[-1]
    # lanes_total = every valid RE lane — exactly what the full sweep solves
    return _row(
        "refresh_incremental",
        round(refresh_ms, 1),
        [round(s, 1) for s in spread],
        _unit_refresh(last.lanes_solved, last.lanes_total, full_ms),
    )


def bench_search_throughput() -> dict:
    """GP-tournament model search vs one-config-per-solve, back to back in
    THIS process (ISSUE 20). One synthetic logistic dataset; the tournament
    pushes rounds x lane_budget hyperparameter configs through vmapped lane
    solves (GP ask/tell overlapped with the device work), while the
    sequential baseline pushes the SAME number of configs through the same
    driver one lane at a time (Sobol asks — no GP fits charged to it, so
    the comparison isolates dispatch granularity, the vmapped-lane lever).
    Row value is tournament configs/sec (median-of-GATE_REPS); the unit
    embeds the same-run sequential rate. Rates compare within the run only
    (chip lottery)."""
    import jax

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.hyperparameter.search_driver import (
        parse_search_space,
        run_model_search,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    rounds, lanes = 3, 8
    n_cfg = rounds * lanes
    x, y = _make_data(2048, 32, seed=29)
    xv, yv = _make_data(1024, 32, seed=31)
    batch = LabeledPointBatch.create(jax.device_put(x), jax.device_put(y))
    val = LabeledPointBatch.create(jax.device_put(xv), jax.device_put(yv))
    space = parse_search_space("lambda=1e-3:1e2:log,alpha=0:1")
    opt = OptimizerConfig(max_iterations=16)

    def tournament() -> None:
        run_model_search(
            batch, val, TaskType.LOGISTIC_REGRESSION, space,
            rounds=rounds, lane_budget=lanes, optimizer=opt,
            seed=5, searcher="gp", evaluator="AUC",
        )

    def sequential() -> None:
        run_model_search(
            batch, val, TaskType.LOGISTIC_REGRESSION, space,
            rounds=n_cfg, lane_budget=1, optimizer=opt,
            seed=5, searcher="sobol", evaluator="AUC",
        )

    # warm both lane-width signatures (L=8 and L=1 solve + metric programs)
    # outside the timings
    tournament()
    sequential()

    t0 = time.perf_counter()
    sequential()
    seq_rate = n_cfg / (time.perf_counter() - t0)

    def once() -> float:
        t0 = time.perf_counter()
        tournament()
        return n_cfg / (time.perf_counter() - t0)

    rate, spread = median_spread(once)
    return _row(
        "search_throughput",
        round(rate, 1),
        [round(s, 1) for s in spread],
        _unit_search(seq_rate),
    )


def bench_cpu_scipy(x, y) -> float:
    """scipy L-BFGS-B example-iters/sec over the same λ grid, sequential.
    Iteration-normalized so vs_baseline compares per-unit-work throughput —
    the two solvers terminate after different iteration counts (the TPU
    lanes stop when line search stalls at the optimum; scipy honors
    maxiter), and raw wall-clock would conflate that with hardware speed."""
    from scipy.optimize import minimize

    x64, y64 = x.astype(np.float64), y.astype(np.float64)

    def run_one(lam: float) -> int:
        def f(w):
            m = x64 @ w
            val = np.sum(np.logaddexp(0.0, m) - y64 * m) + 0.5 * lam * np.dot(w, w)
            p = 1.0 / (1.0 + np.exp(-m))
            g = x64.T @ (p - y64) + lam * w
            return val, g

        res = minimize(f, np.zeros(x.shape[1]), jac=True, method="L-BFGS-B",
                       options={"maxiter": MAX_ITER, "ftol": 0.0, "gtol": 0.0})
        return max(int(res.nit), 1)

    t0 = time.perf_counter()
    total_iters = sum(run_one(lam) for lam in _grid(GRID))
    elapsed = time.perf_counter() - t0
    return len(x64) * total_iters / elapsed


def main():
    from photon_ml_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    x, y = _make_data(N, D)

    tpu_time, tpu_spread, lane_iters = bench_tpu(x, y)
    extra = bench_hot_loop_bandwidth(x[: 1 << 17], y[: 1 << 17])
    extra.extend(bench_game_sweep())
    extra.append(bench_sparse_fe())
    extra.append(bench_sparse_fe_hybrid())
    extra.append(bench_game_sweep_composed())
    extra.append(bench_sparse_fe_1e8())
    extra.append(bench_stream_fe_chunked())
    extra.append(bench_stream_game_duhl())
    extra.append(bench_stream_game_ranks())
    extra.append(bench_serve_microbatch())
    extra.append(bench_refresh_incremental())
    extra.append(bench_search_throughput())
    cpu_rate = bench_cpu_scipy(x[:CPU_SUBSAMPLE], y[:CPU_SUBSAMPLE])

    rate = N * lane_iters / tpu_time
    report = _row(
        "glm_lambda_grid_example_iters_per_sec",
        round(rate, 1),
        [round(N * lane_iters / s, 1) for s in tpu_spread[::-1]],
        _unit_primary(lane_iters, tpu_time),
    )
    report["vs_baseline"] = round(rate / cpu_rate, 2)
    report["extra_metrics"] = extra
    # optional structured journal (stdout contract unchanged: ONE JSON line).
    # Calibration rows are chip-lottery-sensitive — compare fractions of the
    # same-run stream probe, never absolute GB/s across journals.
    telemetry_dir = os.environ.get("PHOTON_TELEMETRY_DIR")
    if telemetry_dir:
        from photon_ml_tpu.telemetry import RunJournal

        # the full unslimmed report rides a sidecar the doctor prefers
        # over the tail-captured line (ISSUE 12)
        write_sidecar(
            report, telemetry_dir,
            config={"n": N, "d": D, "grid": GRID, "max_iter": MAX_ITER},
        )
        with RunJournal(telemetry_dir, filename="bench-journal.jsonl") as journal:
            journal.record("config", n=N, d=D, grid=GRID, max_iter=MAX_ITER)
            for row in extra:
                kind = (
                    "calibration" if "stream" in row["metric"] else "bench_metric"
                )
                journal.record(kind, **row)
            journal.record("bench_metric", **{
                k: v for k, v in report.items() if k != "extra_metrics"
            })
    line = render_report(report)
    # the driver tails 2,000 bytes; an over-budget line would lose the
    # primary metric from the official record (BENCH_r04/r05 regression).
    # A hard raise, not an assert — `python -O` must not strip the guard.
    if len(line.encode()) >= MAX_LINE_BYTES:
        raise RuntimeError(
            f"bench JSON line is {len(line.encode())} bytes "
            f"(>= {MAX_LINE_BYTES}); slim the unit builders"
        )
    print(line)


if __name__ == "__main__":
    main()
