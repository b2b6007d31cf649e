"""Tier-1 guard: the static parity lints (dev/lint_parity.py) stay clean.

The lint enforces two CLAUDE.md conventions: every photon_ml_tpu module
docstring cites its reference file (the SURVEY.md §2 parity contract), and
no module calls the batch-serializing jnp.linalg decompositions outside the
approved paths (BASELINE.md r5 Gauss-Jordan study).
"""

from __future__ import annotations

import ast
import pathlib
import re
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_lint_parity_clean():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "dev" / "lint_parity.py")],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, (
        f"parity lint violations:\n{proc.stdout}{proc.stderr}"
    )
    assert "clean" in proc.stdout


def test_lint_catches_banned_linalg(tmp_path):
    """The AST check actually fires: a module calling jnp.linalg.cholesky
    outside the allowlist is reported with file:line."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    pkg = tmp_path / "photon_ml_tpu" / "sub"
    pkg.mkdir(parents=True)
    (tmp_path / "photon_ml_tpu" / "good.py").write_text(
        '"""Cites Foo.scala:12."""\n'
        "import numpy as np\n"
        "def g(h):\n"
        "    return np.linalg.cholesky(h)  # host numpy: allowed\n"
    )
    (pkg / "bad.py").write_text(
        '"""No reference analogue."""\n'
        "import jax.numpy as jnp\n"
        "def f(h):\n"
        "    return jnp.linalg.cholesky(h)\n"
    )
    (pkg / "aliased.py").write_text(
        '"""No reference analogue."""\n'
        "from jax.numpy import linalg\n"
        "def f(h, b):\n"
        "    return linalg.solve(h, b)\n"
    )
    (pkg / "undocumented.py").write_text("x = 1\n")
    problems = lint_parity.run_lints(tmp_path)
    assert any("bad.py:4" in p and "cholesky" in p for p in problems)
    assert any("aliased.py:4" in p and "solve" in p for p in problems)
    assert any("undocumented.py:1" in p and "docstring" in p for p in problems)
    assert not any("good.py" in p for p in problems)  # np.linalg not banned


def test_lint_catches_cli_full_reads_and_score_allgathers(tmp_path):
    """The partitioned-I/O lints fire: direct read_merged in cli/ and
    process_allgather outside the model-sized allowlist are reported;
    the dispatcher call and allowlisted helpers stay clean."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    cli = tmp_path / "photon_ml_tpu" / "cli"
    cli.mkdir(parents=True)
    (cli / "bad_driver.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "from photon_ml_tpu.io.data_reader import read_merged\n"
        "def run(p, cfg):\n"
        "    return read_merged(p, cfg)\n"
    )
    (cli / "good_driver.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "from photon_ml_tpu.io.partitioned_reader import read_partitioned\n"
        "def run(p, cfg):\n"
        "    return read_partitioned(p, cfg)\n"
    )
    par = tmp_path / "photon_ml_tpu" / "parallel"
    par.mkdir(parents=True)
    (par / "funnel.py").write_text(
        '"""No reference analogue."""\n'
        "from jax.experimental import multihost_utils\n"
        "def gather_scores(scores):\n"
        "    return multihost_utils.process_allgather(scores, tiled=True)\n"
        "def _host_scores(scores):\n"
        "    # allowlisted NAME but wrong FILE: still banned\n"
        "    return multihost_utils.process_allgather(scores, tiled=True)\n"
    )
    (par / "distributed.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "from jax.experimental import multihost_utils\n"
        "def _host_scores(scores):\n"
        "    return multihost_utils.process_allgather(scores, tiled=True)\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("bad_driver.py:2" in p and "read_merged" in p for p in problems)
    assert any("bad_driver.py:4" in p for p in problems)
    assert not any("good_driver.py" in p for p in problems)
    assert any("funnel.py:4" in p and "process_allgather" in p
               for p in problems)
    assert any("funnel.py:7" in p for p in problems)  # wrong file
    assert not any("distributed.py" in p for p in problems)  # allowlisted


def test_lint_catches_pallas_in_vmapped_solve_modules(tmp_path):
    """Check 6 fires: use_pallas=True literals, pallas_call references, and
    pallas imports inside optim/ or algorithm/ (the vmapped solve modules)
    are reported; the same code outside those modules stays clean."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    opt = tmp_path / "photon_ml_tpu" / "optim"
    opt.mkdir(parents=True)
    (opt / "bad_solver.py").write_text(
        '"""No reference analogue."""\n'
        "from jax.experimental import pallas as pl\n"
        "def f(obj, batch):\n"
        "    return obj.bind(batch, use_pallas=True)\n"
        "def k(fn, x):\n"
        "    return pl.pallas_call(fn)(x)\n"
    )
    alg = tmp_path / "photon_ml_tpu" / "algorithm"
    alg.mkdir(parents=True)
    (alg / "clean_solver.py").write_text(
        '"""No reference analogue."""\n'
        "def f(obj, batch):\n"
        "    # the forced-off convention (ops/objective.py) passes\n"
        "    return obj.bind(batch, use_pallas=False)\n"
    )
    ops = tmp_path / "photon_ml_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "kernel_home.py").write_text(
        '"""No reference analogue."""\n'
        "from jax.experimental import pallas as pl\n"
        "def k(fn, x):\n"
        "    return pl.pallas_call(fn)(x)  # un-vmapped module: allowed\n"
        "def force(obj, batch):\n"
        "    return obj.bind(batch, use_pallas=True)\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("bad_solver.py:2" in p and "pallas import" in p for p in problems)
    assert any("bad_solver.py:4" in p and "use_pallas=True" in p for p in problems)
    assert any("bad_solver.py:6" in p and "pallas_call" in p for p in problems)
    assert not any("clean_solver.py" in p for p in problems)
    assert not any("kernel_home.py" in p for p in problems)


def test_lint_catches_segment_sum_without_num_segments(tmp_path):
    """Check 7 fires: segment_sum calls in ops/ or parallel/ missing an
    explicit num_segments are reported; keyword or third-positional counts
    pass, and modules outside the checked packages are not the lint's
    business."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    ops = tmp_path / "photon_ml_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "bad_ops.py").write_text(
        '"""No reference analogue."""\n'
        "import jax\n"
        "def f(v, ids):\n"
        "    return jax.ops.segment_sum(v, ids)\n"
        "def g(v, ids, n):\n"
        "    return jax.ops.segment_sum(v, ids, num_segments=n)\n"
        "def h(v, ids, n):\n"
        "    return jax.ops.segment_sum(v, ids, n)  # positional: explicit\n"
    )
    par = tmp_path / "photon_ml_tpu" / "parallel"
    par.mkdir(parents=True)
    (par / "bad_parallel.py").write_text(
        '"""No reference analogue."""\n'
        "from jax.ops import segment_sum\n"
        "def f(v, ids):\n"
        "    return segment_sum(v, ids, indices_are_sorted=True)\n"
    )
    ev = tmp_path / "photon_ml_tpu" / "evaluation"
    ev.mkdir(parents=True)
    (ev / "outside.py").write_text(
        '"""No reference analogue."""\n'
        "import jax\n"
        "def f(v, ids):\n"
        "    return jax.ops.segment_sum(v, ids)  # outside ops/ + parallel/\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("bad_ops.py:4" in p and "num_segments" in p for p in problems)
    assert not any("bad_ops.py:6" in p for p in problems)
    assert not any("bad_ops.py:8" in p for p in problems)
    assert any("bad_parallel.py:4" in p for p in problems)
    assert not any("outside.py" in p for p in problems)


def test_lint_catches_broad_excepts(tmp_path):
    """The broad-except check fires on swallowing handlers, and exempts
    re-raising handlers and the resilience classifier's allowlist."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    pkg = tmp_path / "photon_ml_tpu" / "io"
    pkg.mkdir(parents=True)
    (pkg / "swallower.py").write_text(
        '"""No reference analogue."""\n'
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        return None\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        pass\n"
    )
    (pkg / "reraiser.py").write_text(
        '"""No reference analogue."""\n'
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except BaseException:\n"
        "        cleanup()\n"
        "        raise\n"
        "def typed(e=None):\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        return None  # typed: not the lint's business\n"
    )
    res = tmp_path / "photon_ml_tpu" / "resilience"
    res.mkdir(parents=True)
    (res / "policy.py").write_text(
        '"""No reference analogue."""\n'
        "def call():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        return None  # allowlisted (file, function)\n"
        "def other():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        return None  # allowlisted file, WRONG function\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("swallower.py:5" in p and "broad except" in p for p in problems)
    assert any("swallower.py:10" in p for p in problems)
    assert not any("reraiser.py" in p for p in problems)
    assert not any("policy.py:5" in p for p in problems)  # allowlisted
    assert any("policy.py:10" in p for p in problems)  # wrong function


def test_lint_catches_dead_end_flag_rejections(tmp_path):
    """Check 8 fires: a cli/ guard rejecting a flag COMBINATION without
    pointing at the composing alternative is reported; rejections that
    name an actionable alternative pass, plain (non-combination)
    requirement messages are not the lint's business, and modules outside
    cli/ are not scanned."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    cli = tmp_path / "photon_ml_tpu" / "cli"
    cli.mkdir(parents=True)
    (cli / "bad_driver.py").write_text(
        '"""No reference analogue."""\n'
        "def validate(problems):\n"
        "    raise ValueError(\n"
        "        'flag A cannot combine with flag B'\n"
        "    )\n"
        "def validate2(problems):\n"
        "    problems.append('X and Y are mutually exclusive')\n"
        "def ok(problems):\n"
        "    raise ValueError(\n"
        "        'flag A cannot combine with flag B — drop B or use C'\n"
        "    )\n"
        "def ok2(problems):\n"
        "    problems.append('--foo requires --bar')  # not a combination\n"
    )
    elsewhere = tmp_path / "photon_ml_tpu" / "io"
    elsewhere.mkdir(parents=True)
    (elsewhere / "outside.py").write_text(
        '"""No reference analogue."""\n'
        "def f():\n"
        "    raise ValueError('a cannot combine with b')  # not cli/\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("bad_driver.py:3" in p and "dead-end" in p for p in problems)
    assert any("bad_driver.py:7" in p for p in problems)
    assert not any("bad_driver.py:9" in p for p in problems)
    assert not any("bad_driver.py:13" in p for p in problems)
    assert not any("outside.py" in p for p in problems)


def test_lint_catches_streaming_jit_closures(tmp_path):
    """Check 9 fires: in the streaming modules, a jit built inside a
    function (closure risk over chunk-sized arrays — the closed-over-batch
    landmine) is reported, as is a module-level jit whose signature lacks
    the chunk 'batch' argument; the sanctioned module-scope
    decorator-with-batch form passes, and non-streaming modules are not
    scanned."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    io_pkg = tmp_path / "photon_ml_tpu" / "io"
    io_pkg.mkdir(parents=True)
    (io_pkg / "stream_reader.py").write_text(
        '"""Cites AvroDataReader.scala:1."""\n'
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('objective',))\n"
        "def good_step(acc, batch, *, objective):\n"
        "    return acc + objective(batch)\n"
        "@jax.jit\n"
        "def bad_no_batch(acc, w):\n"
        "    return acc + w\n"
        "def bad_nested(chunks, w):\n"
        "    step = jax.jit(lambda acc: acc + chunks[0] @ w)\n"
        "    return step(0.0)\n"
    )
    alg = tmp_path / "photon_ml_tpu" / "algorithm"
    alg.mkdir(parents=True)
    (alg / "other.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "import jax\n"
        "def not_scanned(x):\n"
        "    return jax.jit(lambda v: v)(x)  # not a streaming module\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any(
        "stream_reader.py:8" in p and "batch" in p for p in problems
    ), problems
    assert any(
        "stream_reader.py:11" in p and "nested" in p for p in problems
    ), problems
    assert not any("good_step" in p for p in problems)
    # other.py escapes CHECK 9 (not a streaming module) but its raw
    # jax.jit in algorithm/ is exactly what check 13 exists to catch
    assert not any("other.py" in p and "nested" in p for p in problems)
    assert any(
        "other.py" in p and "check 13" in p for p in problems
    ), problems


def test_lint_covers_streaming_game_module(tmp_path):
    """Check 9 scans algorithm/streaming_game.py (the ISSUE 11 streamed
    GAME path): a nested jit there is reported — the closure ban stays
    structural on the new path — while the sanctioned module-scope
    decorator-with-batch form passes."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    alg = tmp_path / "photon_ml_tpu" / "algorithm"
    alg.mkdir(parents=True)
    (alg / "streaming_game.py").write_text(
        '"""Cites CoordinateDescent.scala:1."""\n'
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('objective',))\n"
        "def good_step(table, batch, *, objective):\n"
        "    return table + objective(batch)\n"
        "def bad_nested(chunk, table):\n"
        "    step = jax.jit(lambda t: t + chunk['features'].sum())\n"
        "    return step(table)\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any(
        "streaming_game.py:8" in p and "nested" in p for p in problems
    ), problems
    assert not any(
        "streaming_game.py" in p and "good_step" in p for p in problems
    )


def test_lint_catches_serving_jit_closures(tmp_path):
    """Check 9 covers photon_ml_tpu/serving/: a jit built inside a
    serving-module function (closure risk over the resident model's device
    arrays — the same closure mistake as chunks) is reported; the
    reviewed JIT_CLOSURE_ALLOWED construction site
    (ResidentScorer.__init__, params enter as arguments) passes, and a
    same-named method on another class does NOT inherit the exemption."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    serving = tmp_path / "photon_ml_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "resident.py").write_text(
        '"""Cites GameTransformer.scala:156."""\n'
        "from photon_ml_tpu.telemetry.program_ledger import ledger_jit\n"
        "class ResidentScorer:\n"
        "    def __init__(self, impl):\n"
        "        self._program = ledger_jit(impl, label='serve/score')\n"
        "class Rogue:\n"
        "    def __init__(self, impl, model):\n"
        "        self._program = ledger_jit(lambda d: impl(d, model),\n"
        "                                   label='serve/rogue')\n"
    )
    (serving / "batching.py").write_text(
        '"""Cites GameScoringDriver.scala:133."""\n'
        "import jax\n"
        "def serve(scorer, batch):\n"
        "    return jax.jit(lambda: scorer(batch))()\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any(
        "resident.py:8" in p and "serving" in p for p in problems
    ), problems
    assert any("batching.py:4" in p for p in problems), problems
    assert not any("resident.py:5" in p for p in problems), problems


def test_lint_catches_raw_jit_in_hot_packages(tmp_path):
    """Check 13: a raw jax.jit (attribute or `from jax import jit` name)
    in algorithm/, serving/ or parallel/ is reported — hot programs must
    carry a ledger label (ledger_jit) so the program ledger can attribute
    their compiles — while ledger_jit sites pass, packages outside the
    three prefixes are not scanned, and a class-qualified RAW_JIT_ALLOWED
    entry exempts exactly its own scope."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    alg = tmp_path / "photon_ml_tpu" / "algorithm"
    alg.mkdir(parents=True)
    (alg / "hot.py").write_text(
        '"""Cites CoordinateDescent.scala:1."""\n'
        "import jax\n"
        "from functools import partial\n"
        "from jax import jit as fast\n"
        "from photon_ml_tpu.telemetry.program_ledger import ledger_jit\n"
        "@partial(ledger_jit, label='coord/good', static_argnums=(0,))\n"
        "def good(objective, w):\n"
        "    return w\n"
        "@partial(jax.jit, static_argnums=(0,))\n"
        "def bad_attr(objective, w):\n"
        "    return w\n"
        "def bad_alias(w):\n"
        "    return fast(lambda v: v)(w)\n"
        "class Reviewed:\n"
        "    def __init__(self):\n"
        "        self._p = jax.jit(lambda v: v)\n"
    )
    ops = tmp_path / "photon_ml_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "kernel.py").write_text(
        '"""Cites ValueAndGradientAggregator.scala:1."""\n'
        "import jax\n"
        "@jax.jit\n"
        "def fine(w):\n"
        "    return w  # ops/ is outside the check-13 packages\n"
    )
    problems = lint_parity.check_raw_jit_sites(tmp_path)
    assert any("hot.py:9" in p and "check 13" in p for p in problems), problems
    assert any("hot.py:13" in p for p in problems), problems
    assert any("hot.py:16" in p for p in problems), problems
    assert not any("good" in p for p in problems)
    assert not any("kernel.py" in p for p in problems)

    lint_parity.RAW_JIT_ALLOWED.add(
        ("photon_ml_tpu/algorithm/hot.py", "Reviewed.__init__")
    )
    try:
        allowed = lint_parity.check_raw_jit_sites(tmp_path)
        assert not any("hot.py:16" in p for p in allowed), allowed
        assert any("hot.py:9" in p for p in allowed)
    finally:
        lint_parity.RAW_JIT_ALLOWED.discard(
            ("photon_ml_tpu/algorithm/hot.py", "Reviewed.__init__")
        )


def test_lint_catches_ungated_checkpoint_saves(tmp_path):
    """Check 10 fires: a direct checkpointer.save()/save_progress() in a
    parallel/ or algorithm/ training-loop module is reported (multi-rank
    writes must ride io.checkpoint.commit_checkpoint); the commit-helper
    call itself passes, unrelated .save() receivers (index maps, models)
    pass, and modules outside the training-loop packages are not
    scanned."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    par = tmp_path / "photon_ml_tpu" / "parallel"
    par.mkdir(parents=True)
    (par / "trainer.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "from photon_ml_tpu.io.checkpoint import commit_checkpoint\n"
        "def sweep(checkpointer, ckpt, imap, arrays, meta, exchange):\n"
        "    checkpointer.save(1, arrays, meta)\n"
        "    ckpt.save_progress(fingerprint={}, lam_index=0)\n"
        "    self_like = object()\n"
        "    commit_checkpoint(checkpointer, 1, arrays, meta,\n"
        "                      exchange=exchange)\n"
        "    imap.save('dir', 'shard')  # not a checkpointer\n"
    )
    alg = tmp_path / "photon_ml_tpu" / "algorithm"
    alg.mkdir(parents=True)
    (alg / "cd.py").write_text(
        '"""Cites Foo.scala:1."""\n'
        "def loop(self):\n"
        "    self.checkpointer.save(2, {}, {})\n"
    )
    io_pkg = tmp_path / "photon_ml_tpu" / "io"
    io_pkg.mkdir(parents=True)
    (io_pkg / "checkpoint.py").write_text(
        '"""No reference analogue."""\n'
        "def commit_checkpoint(checkpointer, step, arrays, meta):\n"
        "    return checkpointer.save(step, arrays, meta)  # the helper\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any(
        "trainer.py:4" in p and "commit_checkpoint" in p for p in problems
    ), problems
    assert any("trainer.py:5" in p for p in problems)
    assert any("cd.py:3" in p for p in problems)
    assert not any("trainer.py:9" in p for p in problems)  # imap.save
    assert not any("checkpoint.py" in p for p in problems)  # io/ helper


def test_lint_catches_time_time_durations(tmp_path):
    """Check 11 fires: time.time() (module attribute or from-import alias)
    anywhere in photon_ml_tpu/ outside the reviewed absolute-timestamp
    allowlist is reported; the allowlisted class-QUALIFIED journal
    ``RunJournal.record`` ts site passes, perf_counter is never the
    lint's business, and neither a same-named function in another file
    nor another method of the same name in the allowlisted file inherits
    the exemption."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    pkg = tmp_path / "photon_ml_tpu" / "util"
    pkg.mkdir(parents=True)
    (pkg / "durations.py").write_text(
        '"""No reference analogue."""\n'
        "import time\n"
        "from time import time as now\n"
        "import time as clock\n"
        "def f():\n"
        "    t0 = time.time()\n"
        "    return time.time() - t0  # a duration from wall clock\n"
        "def g():\n"
        "    return now()\n"
        "def ok():\n"
        "    return time.perf_counter()\n"
        "def h():\n"
        "    return clock.time()  # module-aliased: still wall clock\n"
        "class RunJournal:\n"
        "    def record(self):\n"
        "        # allowlisted QUALIFIED name but wrong FILE: still banned\n"
        "        return time.time()\n"
    )
    tel = tmp_path / "photon_ml_tpu" / "telemetry"
    tel.mkdir(parents=True)
    (tel / "journal.py").write_text(
        '"""No reference analogue."""\n'
        "import time\n"
        "class RunJournal:\n"
        "    def record(self):\n"
        "        return {'ts': time.time()}  # the reviewed absolute stamp\n"
        "class Spool:\n"
        "    def record(self):\n"
        "        # allowlisted file + bare method name, WRONG class\n"
        "        return time.time()\n"
    )
    problems = lint_parity.run_lints(tmp_path)
    assert any("durations.py:6" in p and "time.time()" in p
               for p in problems), problems
    assert any("durations.py:7" in p for p in problems)
    assert any("durations.py:9" in p for p in problems)  # from-import alias
    assert not any("durations.py:11" in p for p in problems)  # perf_counter
    assert any("durations.py:13" in p for p in problems)  # module alias
    assert any("durations.py:17" in p for p in problems)  # wrong file
    assert not any("journal.py:5" in p for p in problems)  # allowlisted
    assert any("journal.py:9" in p for p in problems)  # wrong class


def _lint_module_tree():
    return ast.parse((REPO_ROOT / "dev" / "lint_parity.py").read_text())


def test_run_lints_runs_every_check_it_defines():
    """Thirteen ``check_*`` functions, and ``run_lints`` calls each of them
    once: a check that is written but not wired in polices nothing."""
    tree = _lint_module_tree()
    defined = sorted(
        n.name for n in tree.body
        if isinstance(n, ast.FunctionDef) and n.name.startswith("check_")
    )
    run_lints = next(
        n for n in tree.body
        if isinstance(n, ast.FunctionDef) and n.name == "run_lints"
    )
    called = sorted(
        n.func.id for n in ast.walk(run_lints)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
        and n.func.id.startswith("check_")
    )
    assert called == defined
    assert len(defined) == 13


def test_retired_check_number_is_not_reused():
    """Check 12 went with ``bench.py`` (PR 29). CLAUDE.md, docstrings and
    tests cite the checks by number, so the others keep theirs: the
    docstring still counts 1 to 14, 12 is a one-line marker, and no
    message in the module cites a check 12."""
    source = (REPO_ROOT / "dev" / "lint_parity.py").read_text()
    doc = ast.get_docstring(_lint_module_tree())
    numbered = re.findall(r"^(\d+)\. (.*)$", doc, flags=re.M)
    assert [int(n) for n, _ in numbered] == list(range(1, 15))
    titles = dict(numbered)
    assert titles["12"].startswith("(removed")
    assert all(t.startswith("**") for n, t in numbered if n != "12")
    assert "check 12" not in source.replace(titles["12"], "")


def test_lint_catches_resident_param_mutation_outside_swap(tmp_path):
    """Check 14 fires: an assignment to a resident-param attribute
    (.model, the params caches) anywhere in serving/ outside the
    class-qualified swap allowlist is flagged; the sanctioned
    ResidentScorer.__init__ / swap_model scopes pass, as do same-named
    attributes outside serving/."""
    sys.path.insert(0, str(REPO_ROOT / "dev"))
    try:
        import lint_parity
    finally:
        sys.path.pop(0)

    serving = tmp_path / "photon_ml_tpu" / "serving"
    serving.mkdir(parents=True)
    (serving / "resident.py").write_text(
        '"""No reference analogue."""\n'
        "class ResidentScorer:\n"
        "    def __init__(self, model):\n"
        "        self.model = model\n"  # allowlisted
        "        self._params_cache = {}\n"  # allowlisted
        "    def swap_model(self, new_model):\n"
        "        self.model = new_model\n"  # allowlisted
        "        self._bf16_params_cache = {}\n"  # allowlisted
        "    def sneaky(self, new_model):\n"
        "        self.model = new_model\n"  # line 10: banned
        "        self._params_cache = {}\n"  # line 11: banned
        "    def tuple_sneak(self, m, k):\n"
        "        self.model, self._kinds = m, k\n"  # line 13: banned x2
        "class Other:\n"
        "    def swap_model(self, m):\n"
        "        # same method NAME, wrong class: still banned\n"
        "        self.model = m\n"  # line 15: banned
    )
    (serving / "batching.py").write_text(
        '"""No reference analogue."""\n'
        "class MicroBatchServer:\n"
        "    def __init__(self, scorer):\n"
        "        self.scorer = scorer\n"  # not a resident-param attr
        "    def hijack(self, m):\n"
        "        self.scorer.model = m\n"  # line 6: banned
    )
    outside = tmp_path / "photon_ml_tpu" / "parallel"
    outside.mkdir(parents=True)
    (outside / "scoring.py").write_text(
        '"""No reference analogue."""\n'
        "class DistributedScorer:\n"
        "    def swap_model_params(self, m):\n"
        "        self.model = m\n"  # outside serving/: out of scope
    )
    problems = lint_parity.check_resident_param_mutations(tmp_path)
    assert any("resident.py:10" in p and "check 14" in p
               for p in problems), problems
    assert any("resident.py:11" in p for p in problems)
    # tuple unpacking must not slip the ban (both attrs flagged)
    assert sum("resident.py:13" in p for p in problems) == 2, problems
    assert any("resident.py:17" in p for p in problems)
    assert any("batching.py:6" in p for p in problems)
    assert not any("resident.py:4" in p or "resident.py:5" in p
                   or "resident.py:7" in p or "resident.py:8" in p
                   for p in problems)
    assert not any("batching.py:4" in p for p in problems)
    assert not any("scoring.py" in p for p in problems)
    # the real serving package is clean under the real allowlist
    assert lint_parity.check_resident_param_mutations(REPO_ROOT) == []
