"""obsnet: structured runtime observability for sparknet_tpu.

The runtime complement of the two static engines (graftlint lints what
the source promises, graphcheck audits what the lowered graphs do):
this package records what a RUN actually did — fenced span walls,
per-round training metrics with the comm_model-predicted collective
budget attached, live recompile flags, and every bank_guard evidence
write — as schema-validated JSONL (``obs/schema.py``).

Off by default; arm with ``SPARKNET_OBS=<path>.jsonl``.  With obs off
the instrumented hot paths are bit-identical (same lowered StableHLO,
same dispatch count — pinned by ``tests/test_obs.py``).

CLI: ``python -m sparknet_tpu.obs {report|validate|dryrun}``.  Docs:
``docs/OBSERVABILITY.md``.

This ``__init__`` stays import-light on purpose: ``schema`` is
stdlib-only and never initializes a backend, and the Recorder loads
lazily behind :func:`get_recorder`.
"""

from __future__ import annotations

from sparknet_tpu.obs import schema  # noqa: F401  (stdlib-only)

__all__ = ["schema", "get_recorder", "set_recorder"]


def get_recorder():
    """The process Recorder singleton (lazy; built from SPARKNET_OBS)."""
    from sparknet_tpu.obs.recorder import get_recorder as _get

    return _get()


def set_recorder(rec):
    """Replace the singleton (tests / the dryrun CLI); None resets."""
    from sparknet_tpu.obs.recorder import set_recorder as _set

    return _set(rec)
