"""graftlint: AST static analysis for the repo's TPU execution contracts.

Machine-checks rules that previously lived only as prose — evidence
banking, fenced obs spans, contract-manifest freshness (SparkNet's
equivalent contracts were enforced by Spark around the native solver;
ref: PAPER.md, Moritz et al., arXiv:1511.06051 — here the system must
check them itself).

Three engines share this package and one findings schema:

* graftlint (``core``/``rules``) — AST lint of the SOURCE contracts;
* graphcheck (``graphcheck``/``comm_model``) — static analysis of the
  LOWERED graphs: each parallel mode's train step is lowered on the
  virtual 8-device CPU mesh and audited for comm budget, sharding,
  dtype, and donation against banked manifests (docs/graph_contracts/);
* memcheck (``memcheck``/``mem_model``) — static analysis of what the
  same lowerings hold in MEMORY: an analytic jaxpr-liveness model of
  peak per-device HBM cross-checked against XLA's
  ``memory_analysis()``, pallas-kernel VMEM bounds, banked manifests
  (docs/mem_contracts/), and the batch-fit table the serving engine's
  admission gate prices loads against.

Usage:

    python -m sparknet_tpu.analysis                # default repo scope
    python -m sparknet_tpu.analysis tools bench.py --format json
    python -m sparknet_tpu.analysis --list-rules
    python -m sparknet_tpu.analysis graph [--mode dp] [--json] [--update]
    python -m sparknet_tpu.analysis mem [--mode M] [--json] [--update] [--fit]

Library API: ``lint_paths`` / ``lint_source`` return ``Finding``
records; CI asserts ``not [f for f in findings if not f.suppressed]``
(tests/test_graftlint.py::test_repo_self_lint_is_clean).

IMPORTANT: the analysis modules themselves are stdlib-only at import
time, and nothing on this package's import path may INITIALIZE a jax
backend (no ``jax.devices()``, no compiles): the first backend touch
takes the chip, which belongs to one process at a time, and the linter
must never be that process.  graphcheck honors the same contract by
importing jax lazily inside ``run_graphcheck`` — after pinning the CPU
platform — and by keeping its jax-heavy mode factories in
``sparknet_tpu/parallel/modes.py``, outside this package.
"""

from sparknet_tpu.analysis.core import (  # noqa: F401
    Finding,
    RULES,
    lint_file,
    lint_paths,
    lint_source,
    render_json,
    render_text,
    rule,
)
from sparknet_tpu.analysis import rules as _rules  # noqa: F401  (registers)

__all__ = [
    "Finding",
    "RULES",
    "lint_file",
    "lint_paths",
    "lint_source",
    "render_json",
    "render_text",
    "rule",
]
