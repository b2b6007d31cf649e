"""Not a benchmark: a name kept for one test. The pre-chip ``bench.py`` line,
its grammar and its verdict rules went in PR 29. The one benchmark is
``python3 benchmark/run.py`` in the cells of ``BENCHMARK.json``; ``PERF.md``
says what each cell and metric is.

``tests/benchmark/test_bm_manifest.py`` names this file as its example of a
command word outside the benchmark's paths, and no PR but a ``benchmark`` one
may edit that test: the PR that gives it another example deletes this file.
"""

import sys

if __name__ == "__main__":
    sys.exit(__doc__)
