#!/usr/bin/env python
"""Static parity-convention lints for photon_ml_tpu (CLAUDE.md conventions).

Thirteen checks, numbered 1-14 with 12 retired, all pure-AST (no jax import; runs in milliseconds):

1. **Docstring citations** — every ``photon_ml_tpu/**/*.py`` module (except
   ``__init__.py`` re-export shims) must carry a module docstring that
   either cites a reference source file (``Foo.scala``, ``*.avsc``,
   ``*.java``) or explicitly declares "no reference analogue". This is the
   convention the parity judge checks against SURVEY.md §2.

2. **Forbidden batched decompositions** — XLA's batched small
   decompositions serialize per matrix on TPU (cholesky+cho_solve on
   [2000, 16, 16] = 3.4 ms, LU = 9.0 ms, vs 0.09 ms for the hand-rolled
   vectorized Gauss-Jordan in optim/newton.py — BASELINE.md r5 study), so
   ``jnp.linalg.cholesky`` / ``jnp.linalg.solve`` / ``jnp.linalg.inv`` and
   ``jax.scipy.linalg.cho_*`` calls are banned outside the approved
   modules: ops/variance.py (single-Hessian reference-fidelity path with
   its own size gates) and algorithm/coordinates.py (one shared [k, k]
   Gram solve, not a batch).

3. **Unconditional full reads in cli/** — CLI drivers must ingest through
   the partitioned dispatcher (``io.partitioned_reader.read_partitioned``,
   which delegates to ``read_merged`` single-process): a direct
   ``read_merged`` in a driver silently multiplies the full-input decode
   by the process count on multi-host runs (the r5 host-periphery
   finding; ISSUE 2).

4. **O(n) score gathers** — ``process_allgather`` funnels its operand
   through every host; on score-sized ([n]) arrays that undoes the mesh's
   parallelism and peaks host memory at global size. Calls are banned
   outside the model-sized allowlisted helpers in parallel/distributed.py
   (``_host_scores`` — the documented legacy gather for callers that want
   the full vector — and the ``to_host`` state gathers); new score paths
   go through ``parallel.scoring.DistributedScorer.score_partitioned`` +
   ``io.score_writer.ShardedScoreWriter``.

5. **Broad excepts** — bare ``except:`` / ``except Exception:`` /
   ``except BaseException:`` silently swallow the very failures the
   resilience layer exists to classify (photon_ml_tpu/resilience/errors
   is the ONE reviewed transient-vs-fatal decision point; the r2 "compile
   service flakiness" survived a whole round inside an unattributed catch).
   A broad handler passes only when it RE-RAISES (a ``raise`` statement
   anywhere in the handler — the cleanup-and-propagate pattern) or when
   its (file, function) is on the resilience classifier's reviewed
   allowlist below (capability probes, destructor guards, listener
   isolation).

6. **Pallas in vmapped solve modules** — ``lax.while_loop`` bodies trace
   with UNBATCHED tracers, so a ``pallas_call`` baked into a solver loop
   cannot see the vmap and gets batched into a serial per-lane loop
   (measured 40x slower on the λ-grid, BASELINE.md r4; the reason
   ops/objective.py forces use_pallas=False on every vmapped lane). The
   solver/coordinate modules (``optim/``, ``algorithm/``, estimators.py)
   therefore must not contain a literal ``use_pallas=True`` call keyword,
   any ``pallas_call`` reference, or an import of a pallas module.

7. **segment_sum without num_segments** — a ``jax.ops.segment_sum`` call
   that omits ``num_segments`` infers the segment count from the data,
   silently re-specializing shapes per batch (a fresh compile — ~100 ms
   remote dispatch each — whenever the inferred count changes) and, under
   jit with traced ids, failing outright. Every call in the device hot-path
   packages ``ops/`` and ``parallel/`` must pass the count explicitly
   (keyword or third positional argument).

8. **Dead-end flag rejections in cli/** — a driver-level guard that
   rejects a flag COMBINATION ("cannot combine", "mutually exclusive",
   ...) must tell the operator what to do instead (an actionable verb:
   use/drop/pass/see/disable/read ...). ISSUE 6 turned the
   hybrid x --partitioned-io rejection into a supported composition; the
   rejections that remain must never strand an operator without naming
   the composing alternative or the flag to change.

9. **Nested jit in streaming/serving modules** — every chunk-consuming jit
   in io/stream_reader.py + algorithm/streaming.py +
   algorithm/streaming_game.py must live at module
   scope with the chunk batch in its ARGUMENT list: a jit built inside a
   function can close over chunk-sized arrays, which are baked into the
   program as CONSTANTS — every chunk then a new program (a compile per
   chunk) carrying its own copy of the bytes. The serving package
   (``photon_ml_tpu/serving/``) is under the same ban: closing a jit over
   the resident model's device arrays is exactly the same mistake —
   params must enter the program as ARGUMENTS (pre-placed, donated
   buffers), and the one construction site that does so is reviewed
   explicitly (JIT_CLOSURE_ALLOWED).

10. **Ungated checkpoint writes in training loops** — every
   ``TrainingCheckpointer``/``SolverCheckpointer`` write site in
   ``parallel/`` and ``algorithm/`` must go through
   ``io.checkpoint.commit_checkpoint`` (rank-0-gated per the
   multi-process convention, barrier-committed when a MetadataExchange is
   attached). A bare ``checkpointer.save(...)`` in a training loop lets a
   worker rank race rank 0 on the shared directory, or commit a
   checkpoint for a sweep some rank never finished (ISSUE 8's
   exchange-consistency rule).

11. **time.time() for durations** — ``time.time()`` is wall clock: it
   steps with NTP/host clock adjustments, so differences of its readings
   are not durations (rows ordered by it can even go backwards — the
   reason journal rows carry ``elapsed_ms``). Every duration/ordering
   measurement in ``photon_ml_tpu/`` must use ``time.perf_counter``.
   ``time.time()`` calls are banned outside the reviewed
   absolute-timestamp allowlist (the journal's ``ts`` field, the tracer's
   wall anchor — sites whose OUTPUT is an absolute timestamp, never a
   difference).

12. (removed with ``bench.py``, PR 29)

13. **Raw jit sites in the hot-program packages** — every jit in
   ``algorithm/``, ``serving/`` and ``parallel/`` must route through
   ``telemetry.program_ledger.ledger_jit`` with a stable label (the
   lint-as-memory discipline: labeling hot programs is structural, not
   remembered), or sit on the reviewed class-qualified allowlist. A raw
   ``jax.jit`` there compiles programs the ledger cannot see — its
   recompile attribution, cost accounting, and the serving
   ``replay_compiles == 0`` pin (ISSUE 13) all go blind to that site.

14. **Resident-param mutation outside the guarded swap API** — the
   serving package holds a model resident across requests; swapping it
   in-place is legal ONLY through ``ResidentScorer.swap_model``, whose
   layout fingerprint guard rejects a layout-changing model typed (naming
   the differing leaves) BEFORE any state mutates and re-feeds the
   resident-bytes/HBM-forecast gauges after. An assignment to a
   resident-param attribute (``.model``, the params caches) anywhere else
   in ``photon_ml_tpu/serving/`` would bypass that guard — a silent
   layout change recompiles per request (the bounded-signature contract
   dies) or serves garbage. Class-qualified allowlist, like checks 9-13.

Exit status 0 = clean; 1 = violations (printed one per line as
``path:lineno: message``). Run from the repo root:

    python dev/lint_parity.py
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

PACKAGE = "photon_ml_tpu"

#: a docstring satisfies the convention if it names a reference source file
#: (Foo.scala:NN and friends; dev-scripts/*.py is the reference's one Python
#: tool — a bare .py mention is NOT enough, else self-citations of this
#: package's own modules would pass), a reference module directory
#: (photon-diagnostics diagnostics/hl/ — used by subsystem-level ports), or
#: explicitly declares there is none
CITATION_RE = re.compile(
    r"\.(scala|avsc|java)\b"
    r"|dev-scripts/[\w./-]+\.py\b"
    r"|photon-(lib|api|client|diagnostics|test-utils)\s+[\w./-]+/"
    r"|no reference analogue",
    re.IGNORECASE,
)

#: modules allowed to call the banned decompositions (see module docstring)
LINALG_ALLOWED = {
    f"{PACKAGE}/ops/variance.py",
    f"{PACKAGE}/algorithm/coordinates.py",
}

#: jnp.linalg attributes that batch-serialize on TPU. Host-side numpy
#: (np.linalg.*) is NOT banned — the measured pathology is TPU-only.
BANNED_LINALG = {"cholesky", "solve", "inv", "cho_factor", "cho_solve"}

#: attribute-chain roots that resolve to jax (import jax / import jax.numpy
#: as jnp / import jax.scipy as jsp conventions in this repo)
JAX_ROOTS = {"jax", "jnp", "jsp"}


def _jax_linalg_aliases(tree: ast.AST) -> set[str]:
    """Names bound to a jax linalg MODULE (``from jax.numpy import linalg``
    / ``from jax.scipy import linalg as jla``) — calls through these would
    otherwise produce 2-element chains that escape the root check."""
    aliases: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in (
            "jax.numpy", "jax.scipy", "jax"
        ):
            for a in node.names:
                if a.name == "linalg":
                    aliases.add(a.asname or a.name)
    return aliases


def _attribute_chain(node: ast.Attribute) -> list[str]:
    """`jnp.linalg.solve` -> ["jnp", "linalg", "solve"]."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


def check_docstring_citations(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if path.name == "__init__.py":
            continue  # re-export shims; parity docs live in the modules
        tree = ast.parse(path.read_text())
        doc = ast.get_docstring(tree) or ""
        if not CITATION_RE.search(doc):
            problems.append(
                f"{rel}:1: module docstring cites no reference file "
                "(want e.g. 'Foo.scala:NN' or an explicit "
                "'no reference analogue')"
            )
    return problems


def check_banned_linalg(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel in LINALG_ALLOWED:
            continue
        tree = ast.parse(path.read_text())
        aliases = _jax_linalg_aliases(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            chain = _attribute_chain(node)
            if len(chain) < 2 or chain[-1] not in BANNED_LINALG:
                continue
            # jnp.linalg.solve / jax.numpy.linalg.solve / jsp.linalg.cho_solve
            via_root = (
                len(chain) >= 3 and chain[-2] == "linalg"
                and chain[0] in JAX_ROOTS
            )
            # from jax.numpy import linalg [as X]; X.solve(...)
            via_alias = len(chain) == 2 and chain[0] in aliases
            if via_root or via_alias:
                problems.append(
                    f"{rel}:{node.lineno}: {'.'.join(chain)} — batched "
                    "small decompositions serialize per matrix on TPU; use "
                    "the vectorized Gauss-Jordan path (optim/newton.py / "
                    "ops/variance.py) or add this module to the lint "
                    "allowlist with a measured justification"
                )
    return problems


#: (file, function) pairs whose process_allgather calls are model-sized
#: and reviewed: _host_scores (the documented legacy full-vector gather)
#: and the nested to_host state gathers — a same-named function in any
#: OTHER module does not inherit the exemption
ALLGATHER_ALLOWED = {
    (f"{PACKAGE}/parallel/distributed.py", "_host_scores"),
    (f"{PACKAGE}/parallel/distributed.py", "to_host"),
    # SPMD lane scheduling: per-LANE scalars (entity-table-sized flags and
    # traces, never the [n] sample axis), a collective every rank makes
    (f"{PACKAGE}/algorithm/lane_scheduler.py", "_gather_np"),
}


def check_cli_full_reads(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE / "cli").glob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.ImportFrom) and any(
                a.name == "read_merged" for a in node.names
            ):
                hit = "import of read_merged"
            elif isinstance(node, ast.Name) and node.id == "read_merged":
                hit = "read_merged"
            elif isinstance(node, ast.Attribute) and node.attr == "read_merged":
                hit = "read_merged"
            if hit:
                problems.append(
                    f"{rel}:{node.lineno}: {hit} — CLI drivers must ingest "
                    "through io.partitioned_reader.read_partitioned (it "
                    "delegates to read_merged single-process; a direct "
                    "call multiplies the full decode by the process count)"
                )
    return problems


def check_score_allgathers(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())

        stack: list[str] = []
        hits: list[int] = []

        def visit(node):
            is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            if is_fn:
                stack.append(node.name)
            if (
                (isinstance(node, ast.Attribute)
                 and node.attr == "process_allgather")
                or (isinstance(node, ast.Name)
                    and node.id == "process_allgather")
            ) and not (stack and (rel, stack[-1]) in ALLGATHER_ALLOWED):
                hits.append(node.lineno)
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_fn:
                stack.pop()

        visit(tree)
        for lineno in hits:
            problems.append(
                f"{rel}:{lineno}: process_allgather outside the allowlisted "
                "model-sized helpers — an O(n) score gather funnels the "
                "global vector through every host; use "
                "DistributedScorer.score_partitioned + ShardedScoreWriter, "
                "or put a model-sized gather in an allowlisted helper"
            )
    return problems


#: the resilience classifier's allowlist: (file, function) pairs whose
#: broad excepts are REVIEWED swallows — capability probes whose failure
#: IS the answer, destructor/listener isolation, and the classifier
#: consumers themselves (resilience/policy.py, resilience/recovery.py:
#: their handlers consult classify_exception and re-raise fatal errors).
#: Everything else must catch typed exceptions or re-raise.
BROAD_EXCEPT_ALLOWED = {
    (f"{PACKAGE}/resilience/policy.py", "call"),
    (f"{PACKAGE}/resilience/recovery.py", "run_with_recovery"),
    # the chunk-prefetch producer thread: the retry policy already
    # classified and retried; a thread cannot re-raise usefully, so the
    # handler classifies and FORWARDS the failure to the consumer's
    # stack, which re-raises it attributed (io/stream_reader.py)
    (f"{PACKAGE}/io/stream_reader.py", "_producer"),
    # the program ledger's cost/memory analysis is a capability probe:
    # lower()/cost_analysis()/AOT compile each fail differently per
    # backend, every failure degrades to None fields (logged at debug),
    # and an analysis error must never reach the dispatch path it observes
    (f"{PACKAGE}/telemetry/program_ledger.py", "_analyze"),
    (f"{PACKAGE}/telemetry/journal.py", "_process_index"),
    # same capability probe as the journal's: rank 0 when jax is absent
    (f"{PACKAGE}/telemetry/tracing.py", "_process_index"),
    # driver-teardown trace flush: tracing is observability — an error in
    # a finally must not replace the run's own outcome or skip the
    # journal rows that follow; every error is logged with traceback
    (f"{PACKAGE}/telemetry/tracing.py", "flush_trace_best_effort"),
    (f"{PACKAGE}/io/offheap_index_map.py", "__del__"),
    (f"{PACKAGE}/util/timed.py", "__enter__"),
    (f"{PACKAGE}/util/events.py", "send"),
    (f"{PACKAGE}/cli/game_training_driver.py", "validate"),
    # the serve driver's swap-poller daemon thread: a garbled published
    # model dir can raise beyond the obvious types, the thread has no
    # caller to re-raise to, and one bad publish must never stop all
    # future refreshes — every failure is journaled as a typed
    # `model_swap` rejection and classified for log severity
    (f"{PACKAGE}/cli/serve_driver.py", "scan_once"),
    # the serving micro-batch loop: a batch-level scoring failure routes
    # through classify_exception and falls back to per-request isolation
    # (_isolate), where each request's own failure is classified and
    # forwarded TYPED to that request's future — one poisoned request
    # fails attributed, the loop keeps serving (the chaos-suite contract)
    (f"{PACKAGE}/serving/batching.py", "_flush"),
    (f"{PACKAGE}/serving/batching.py", "_isolate"),
}

_BROAD_NAMES = {"Exception", "BaseException"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:  # bare except:
        return True
    if isinstance(t, ast.Name) and t.id in _BROAD_NAMES:
        return True
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(e, ast.Name) and e.id in _BROAD_NAMES for e in t.elts
        )
    return False


def _reraises(handler: ast.ExceptHandler) -> bool:
    """Any raise in the handler body: cleanup-and-propagate (bare
    ``raise``) or typed transformation (``raise X(...) from e``) — the
    original failure is not swallowed either way."""
    return any(
        isinstance(node, ast.Raise)
        for stmt in handler.body
        for node in ast.walk(stmt)
    )


def check_broad_excepts(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())

        stack: list[str] = []

        def visit(node):
            is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            if is_fn:
                stack.append(node.name)
            if (
                isinstance(node, ast.ExceptHandler)
                and _is_broad(node)
                and not _reraises(node)
                and not (stack and (rel, stack[-1]) in BROAD_EXCEPT_ALLOWED)
            ):
                problems.append(
                    f"{rel}:{node.lineno}: broad except "
                    "(bare/Exception/BaseException) that swallows the "
                    "error — catch typed exceptions, re-raise, or route "
                    "the decision through resilience.classify_exception "
                    "and add the (file, function) to the reviewed "
                    "allowlist in dev/lint_parity.py"
                )
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_fn:
                stack.pop()

        visit(tree)
    return problems


#: modules whose solves are vmapped (per-entity RE/MF buckets, λ-grid
#: lanes): a Pallas kernel reachable from them vmap-batches into a serial
#: per-lane loop (the measured 40x footgun — check 6 above)
VMAPPED_SOLVE_PREFIXES = (
    f"{PACKAGE}/optim/",
    f"{PACKAGE}/algorithm/",
    f"{PACKAGE}/estimators.py",
    # search tournaments (ISSUE 20) dispatch the same vmapped lane solves
    f"{PACKAGE}/hyperparameter/",
)

_PALLAS_MODULE_RE = re.compile(r"(^|\.)pallas(\b|_glm)")


def check_vmapped_pallas(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if not rel.startswith(VMAPPED_SOLVE_PREFIXES):
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if (
                        kw.arg == "use_pallas"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True
                    ):
                        hit = "use_pallas=True"
            elif isinstance(node, ast.Name) and node.id == "pallas_call":
                hit = "pallas_call"
            elif isinstance(node, ast.Attribute) and node.attr == "pallas_call":
                hit = "pallas_call"
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                mods = [a.name for a in node.names]
                if isinstance(node, ast.ImportFrom) and node.module:
                    mods.append(node.module)
                if any(_PALLAS_MODULE_RE.search(m) for m in mods):
                    hit = "pallas import"
            if hit:
                problems.append(
                    f"{rel}:{node.lineno}: {hit} in a vmapped-solve module — "
                    "while_loop bodies trace unbatched, so a baked-in Pallas "
                    "call gets vmap-batched into a serial per-lane loop "
                    "(measured 40x slower); keep use_pallas=False on vmapped "
                    "lanes (ops/objective.py)"
                )
    return problems


#: packages whose segment_sum calls run in device hot paths (check 7); a
#: missing num_segments there silently re-specializes shapes per batch
SEGMENT_SUM_CHECKED_PREFIXES = (
    f"{PACKAGE}/ops/",
    f"{PACKAGE}/parallel/",
)


def check_segment_sum_num_segments(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if not rel.startswith(SEGMENT_SUM_CHECKED_PREFIXES):
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_seg = (
                isinstance(fn, ast.Attribute) and fn.attr == "segment_sum"
            ) or (isinstance(fn, ast.Name) and fn.id == "segment_sum")
            if not is_seg:
                continue
            explicit = len(node.args) >= 3 or any(
                kw.arg == "num_segments" for kw in node.keywords
            )
            if not explicit:
                problems.append(
                    f"{rel}:{node.lineno}: segment_sum without an explicit "
                    "num_segments= — the inferred count re-specializes "
                    "shapes per batch (a fresh remote compile whenever it "
                    "changes) and fails under jit with traced ids; pass "
                    "the static segment count"
                )
    return problems


#: a rejection message is a flag-COMBINATION rejection when it says two
#: things cannot be used together (check 8)
COMBINATION_REJECTION_RE = re.compile(
    r"cannot (?:be )?combined?\b|does not combine|mutually exclusive",
    re.IGNORECASE,
)

#: ...and it escapes the dead-end when it names an actionable alternative
REJECTION_POINTER_RE = re.compile(
    r"\b(use|instead|drop|pass|see|disable|switch|read|set)\b",
    re.IGNORECASE,
)


def _literal_message(call: ast.Call) -> str:
    """Concatenate the string-literal fragments of a call's arguments
    (implicit adjacent-literal concatenation arrives as one Constant;
    f-string constant parts ride JoinedStr values)."""
    parts: list[str] = []
    for arg in call.args:
        for node in ast.walk(arg):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                parts.append(node.value)
    return "".join(parts)


def check_cli_dead_end_rejections(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE / "cli").glob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_guard = (
                isinstance(fn, ast.Name) and fn.id == "ValueError"
            ) or (isinstance(fn, ast.Attribute) and fn.attr == "append")
            if not is_guard:
                continue
            msg = _literal_message(node)
            if not COMBINATION_REJECTION_RE.search(msg):
                continue
            if not REJECTION_POINTER_RE.search(msg):
                problems.append(
                    f"{rel}:{node.lineno}: flag-combination rejection "
                    "without a pointer to the composing alternative — tell "
                    "the operator what to use/drop/change instead (no "
                    "dead-end rejections; see ISSUE 6 / lint check 8)"
                )
    return problems


#: the out-of-core streaming modules (check 9): every chunk-consuming jit
#: must live at module scope with the chunk batch in its ARGUMENT list — a
#: jit built inside a function can close over chunk-sized arrays, which
#: are baked into the program as CONSTANTS (a compile per chunk, each
#: executable carrying its own copy of the bytes)
STREAMING_MODULES = (
    f"{PACKAGE}/io/stream_reader.py",
    f"{PACKAGE}/algorithm/streaming.py",
    # the streamed-GAME path (ISSUE 11): its chunk-consuming jits carry
    # the same closure exposure as the GLM streaming modules
    f"{PACKAGE}/algorithm/streaming_game.py",
    # model-search tournaments (ISSUE 20): the vmapped lane solve and the
    # on-device metric jits take the full train/validation batch — it must
    # ride the argument list, never a closure
    f"{PACKAGE}/algorithm/lane_search.py",
    f"{PACKAGE}/hyperparameter/search_driver.py",
)

#: serving modules join the ban (whole package): the operand at risk is
#: the resident MODEL's device arrays instead of a chunk, same cost
SERVING_MODULE_PREFIX = f"{PACKAGE}/serving/"

#: (file, dotted class-qualified scope) pairs whose jit CONSTRUCTION is
#: reviewed: the resident scorer builds its donated-buffer program once at
#: startup, and BOTH operands — micro-batch data and pre-placed model
#: params — enter it as ARGUMENTS (nothing request- or model-sized is
#: closed over; see the site's comment). Class-qualified so another jit in
#: the same file stays banned.
JIT_CLOSURE_ALLOWED = {
    (f"{PACKAGE}/serving/resident.py", "ResidentScorer.__init__"),
}


#: names check 9 treats as a jit constructor: the raw jax.jit and the
#: ledger's labeled wrapper (telemetry/program_ledger.ledger_jit) — the
#: closure discipline is identical either way (operands must be ARGUMENTS)
_JIT_NAMES = ("jit", "ledger_jit")


def _jit_references(node: ast.AST):
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _JIT_NAMES:
            yield n
        elif isinstance(n, ast.Name) and n.id in _JIT_NAMES:
            yield n


def check_streaming_jit_closures(root: pathlib.Path) -> list[str]:
    problems = []
    paths = [root / rel for rel in STREAMING_MODULES]
    serving_dir = root / SERVING_MODULE_PREFIX
    if serving_dir.is_dir():
        paths.extend(sorted(serving_dir.rglob("*.py")))
    for path in paths:
        if not path.exists():
            continue
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())
        for stmt in tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # module-scope decorator jits are the sanctioned form —
                # compiled once, chunks enter through the argument list —
                # but the chunk batch must actually BE an argument
                deco_jits = [
                    n for d in stmt.decorator_list for n in _jit_references(d)
                ]
                args = {
                    a.arg
                    for a in (
                        stmt.args.posonlyargs
                        + stmt.args.args
                        + stmt.args.kwonlyargs
                    )
                }
                if deco_jits and "batch" not in args:
                    problems.append(
                        f"{rel}:{stmt.lineno}: module-level jit "
                        f"'{stmt.name}' has no 'batch' parameter — the "
                        "chunk must ride the jit's argument list, never a "
                        "closure (a closed-over chunk is a constant of the "
                        "program: a compile per chunk; lint check 9)"
                    )
        problems.extend(_nested_jit_hits(rel, tree))
    return problems


def _nested_jit_hits(rel: str, tree: ast.AST) -> list[str]:
    """jit references outside the sanctioned module-scope-decorator form,
    minus the reviewed JIT_CLOSURE_ALLOWED construction sites (tracked by
    dotted class-qualified scope name)."""
    problems: list[str] = []

    def flag(node) -> None:
        problems.append(
            f"{rel}:{node.lineno}: jit nested inside a function/class in "
            "a streaming/serving module — a jit built per call can close "
            "over chunk- or model-sized arrays, which are baked into the "
            "program as constants (a compile per chunk or per model); "
            "define the jitted step at module scope (or a "
            "reviewed JIT_CLOSURE_ALLOWED site) and pass the operands as "
            "arguments (lint check 9)"
        )

    def scan(node, stack: "tuple[str, ...]") -> None:
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            inner = stack + (node.name,)
            for child in ast.iter_child_nodes(node):
                scan(child, inner)
            return
        is_jit = (
            isinstance(node, ast.Attribute) and node.attr in _JIT_NAMES
        ) or (isinstance(node, ast.Name) and node.id in _JIT_NAMES)
        if is_jit and not (
            stack and (rel, ".".join(stack)) in JIT_CLOSURE_ALLOWED
        ):
            flag(node)
        for child in ast.iter_child_nodes(node):
            scan(child, stack)

    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # decorators are judged by the module-scope 'batch' rule above
            for child in stmt.body:
                scan(child, (stmt.name,))
        else:
            scan(stmt, ())
    return problems


#: training-loop packages whose checkpoint writes must ride the commit
#: helper (check 10); io/ itself (the helper + checkpointer internals)
#: and estimators/cli (single-rank solver checkpointing, rank-gated at
#: the library layer) are out of scope
CHECKPOINT_WRITE_PREFIXES = (
    f"{PACKAGE}/parallel/",
    f"{PACKAGE}/algorithm/",
)

#: a receiver is "a checkpointer" when any identifier in its attribute
#: chain mentions one — matches this repo's naming (checkpointer, ckpt,
#: self.checkpointer); a same-named method on unrelated objects
#: (imap.save, model saves) never matches
_CHECKPOINTER_NAME_RE = re.compile(r"checkpoint|(^|\.)ckpt(\.|$)",
                                   re.IGNORECASE)


def check_checkpoint_commit_sites(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if not rel.startswith(CHECKPOINT_WRITE_PREFIXES):
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute)
                and fn.attr in ("save", "save_progress")
            ):
                continue
            receiver = ".".join(_attribute_chain(fn)[:-1])
            if _CHECKPOINTER_NAME_RE.search(receiver):
                problems.append(
                    f"{rel}:{node.lineno}: direct checkpointer "
                    f"{fn.attr}() in a training-loop module — multi-rank "
                    "checkpoint writes must go through io.checkpoint."
                    "commit_checkpoint (rank-0-gated, barrier-committed; "
                    "lint check 10)"
                )
    return problems


#: (file, dotted class-qualified name) pairs whose ``time.time()`` reads
#: are REVIEWED absolute-timestamp sites (the value is reported as a
#: wall-clock stamp, never differenced): the journal's per-row ``ts`` and
#: the tracer's wall anchor for cross-rank correlation. Class-QUALIFIED so
#: e.g. a time.time() in another __init__ of the same file stays banned.
#: Everything else must use ``time.perf_counter`` (check 11).
TIME_TIME_ALLOWED = {
    (f"{PACKAGE}/telemetry/journal.py", "RunJournal.record"),
    (f"{PACKAGE}/telemetry/tracing.py", "Tracer.__init__"),
}


def check_time_time_durations(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())
        # names bound to time.time by `from time import time [as t]`
        aliases: set[str] = set()
        # names bound to the time MODULE (`import time [as clock]`) so
        # `clock.time()` cannot slip past the receiver-name check
        module_aliases: set[str] = {"time"}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name == "time":
                        aliases.add(a.asname or a.name)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time":
                        module_aliases.add(a.asname or a.name)

        stack: list[str] = []
        hits: list[int] = []

        def visit(node):
            is_scope = isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            )
            if is_scope:
                stack.append(node.name)
            if isinstance(node, ast.Call):
                fn = node.func
                is_time = (
                    isinstance(fn, ast.Attribute)
                    and fn.attr == "time"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in module_aliases
                ) or (isinstance(fn, ast.Name) and fn.id in aliases)
                if is_time and (rel, ".".join(stack)) not in TIME_TIME_ALLOWED:
                    hits.append(node.lineno)
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_scope:
                stack.pop()

        visit(tree)
        for lineno in hits:
            problems.append(
                f"{rel}:{lineno}: time.time() — wall clock steps with host "
                "clock adjustments, so its differences are not durations; "
                "use time.perf_counter for any timing/ordering, or add "
                "this reviewed absolute-timestamp site to "
                "TIME_TIME_ALLOWED in dev/lint_parity.py (check 11)"
            )
    return problems


#: hot-program packages whose jits must carry a ledger label (check 13):
#: a raw jax.jit here compiles programs the ledger cannot attribute
RAW_JIT_PREFIXES = (
    f"{PACKAGE}/algorithm/",
    f"{PACKAGE}/serving/",
    f"{PACKAGE}/parallel/",
    f"{PACKAGE}/hyperparameter/",
)

#: (file, dotted class-qualified scope) pairs whose RAW jax.jit use is
#: reviewed — currently empty: every jit in the checked packages routes
#: through ledger_jit. Add an entry only with a written reason the site
#: cannot carry a label.
RAW_JIT_ALLOWED: set = set()


def check_raw_jit_sites(root: pathlib.Path) -> list[str]:
    problems = []
    for path in sorted((root / PACKAGE).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if not rel.startswith(RAW_JIT_PREFIXES):
            continue
        tree = ast.parse(path.read_text())
        # names bound to jax.jit by `from jax import jit [as j]`
        aliases: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                for a in node.names:
                    if a.name == "jit":
                        aliases.add(a.asname or a.name)

        stack: list[str] = []
        hits: list[int] = []

        def visit(node):
            is_scope = isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            )
            if is_scope:
                stack.append(node.name)
            raw = (
                isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id in JAX_ROOTS
            ) or (isinstance(node, ast.Name) and node.id in aliases)
            if raw and (rel, ".".join(stack)) not in RAW_JIT_ALLOWED:
                hits.append(node.lineno)
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_scope:
                stack.pop()

        visit(tree)
        for lineno in hits:
            problems.append(
                f"{rel}:{lineno}: raw jax.jit in a hot-program package — "
                "route the site through telemetry.program_ledger.ledger_jit "
                "with a stable label so the program ledger can attribute "
                "its compiles (ISSUE 13), or add the class-qualified scope "
                "to RAW_JIT_ALLOWED with a written reason (lint check 13)"
            )
    return problems


#: resident-param attributes whose assignment in serving/ must route
#: through the guarded swap API (check 14): the resident model reference
#: and the layout-keyed params caches it invalidates
RESIDENT_PARAM_ATTRS = {
    "model",
    "_params_cache",
    "_bf16_params_cache",
    "_params_cache_bytes",
    "_kinds",
    "_model_version",
}

#: (file, dotted class-qualified scope) pairs sanctioned to mutate
#: resident params: construction, and the fingerprint-guarded swap
RESIDENT_MUTATION_ALLOWED = {
    (f"{PACKAGE}/serving/resident.py", "ResidentScorer.__init__"),
    (f"{PACKAGE}/serving/resident.py", "ResidentScorer.swap_model"),
}


def check_resident_param_mutations(root: pathlib.Path) -> list[str]:
    problems = []
    serving_dir = root / PACKAGE / "serving"
    if not serving_dir.is_dir():
        return problems
    for path in sorted(serving_dir.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        tree = ast.parse(path.read_text())

        stack: list[str] = []
        hits: list[tuple[int, str]] = []

        def flatten(t):
            # tuple/list unpacking and starred targets must not slip the
            # ban: `self.model, x = ...` mutates resident params too
            if isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    yield from flatten(e)
            elif isinstance(t, ast.Starred):
                yield from flatten(t.value)
            else:
                yield t

        def targets(node):
            raw = []
            if isinstance(node, ast.Assign):
                raw = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                raw = [node.target]
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                raw = [node.target]
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                raw = [
                    item.optional_vars for item in node.items
                    if item.optional_vars is not None
                ]
            return [t for r in raw for t in flatten(r)]

        def visit(node):
            is_scope = isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            )
            if is_scope:
                stack.append(node.name)
            for t in targets(node):
                if (
                    isinstance(t, ast.Attribute)
                    and t.attr in RESIDENT_PARAM_ATTRS
                    and (rel, ".".join(stack)) not in RESIDENT_MUTATION_ALLOWED
                ):
                    hits.append((node.lineno, t.attr))
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_scope:
                stack.pop()

        visit(tree)
        for lineno, attr in hits:
            problems.append(
                f"{rel}:{lineno}: assignment to resident-param attribute "
                f"'.{attr}' outside the guarded swap API — resident-model "
                "mutation in serving/ must go through "
                "ResidentScorer.swap_model (layout-fingerprint-guarded, "
                "gauge-refeeding) or a reviewed "
                "RESIDENT_MUTATION_ALLOWED scope (lint check 14)"
            )
    return problems


def run_lints(root: pathlib.Path | str | None = None) -> list[str]:
    root = pathlib.Path(root) if root else pathlib.Path(__file__).resolve().parents[1]
    return (
        check_docstring_citations(root)
        + check_banned_linalg(root)
        + check_cli_full_reads(root)
        + check_score_allgathers(root)
        + check_broad_excepts(root)
        + check_vmapped_pallas(root)
        + check_segment_sum_num_segments(root)
        + check_cli_dead_end_rejections(root)
        + check_streaming_jit_closures(root)
        + check_checkpoint_commit_sites(root)
        + check_time_time_durations(root)
        + check_raw_jit_sites(root)
        + check_resident_param_mutations(root)
    )


def main() -> int:
    problems = run_lints()
    for p in problems:
        print(p)
    if problems:
        print(f"lint_parity: {len(problems)} violation(s)", file=sys.stderr)
        return 1
    print("lint_parity: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
