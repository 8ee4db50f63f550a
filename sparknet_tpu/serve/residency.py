"""Multi-model HBM admission pricing for the serving engine.

The banked batch-fit table (``docs/mem_contracts/batch_fit.json``)
predicts what a TRAIN step holds on the chip; here it prices model
LOADS: before the engine compiles a single bucket, the model's
worst-case resident footprint is priced off the table and the load is
refused when it would not fit next to the models already resident.  A
refusal costs nothing; an OOM mid-serve takes every resident model
down.

The inference footprint is derived from the banked TRAIN fit (the only
fit the table holds) conservatively:

    inference(b) = max(params_bytes, c0 + c1*b - slots_bytes)

i.e. the train-step prediction at the model's LARGEST bucket, minus the
optimizer slots a forward never allocates, floored at the raw param
bytes.  The train c0/c1 terms still over-count inference activations
(no backward residency at serve time), which is the right direction for
an admission gate: every refusal it issues, the train fit would refuse
harder.  Arms are priced at the f32 row regardless of deploy dtype —
fold-BN keeps param bytes (minus two vectors per fold) and int8 shrinks
them; pricing the f32 ceiling keeps the gate conservative for all arms.

Deliberately stdlib-only + mem_model (the analysis-package contract):
importable with no jax, usable by tests that never touch a backend.
"""

from __future__ import annotations

import json
import os

from sparknet_tpu.analysis.mem_model import (
    HBM_USABLE_FRAC,
    V5E_HBM_BYTES,
    predicted_bytes,
)

__all__ = [
    "FIT_TABLE_PATH",
    "AdmissionPolicy",
    "load_fit_table",
    "price_residency",
]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIT_TABLE_PATH = os.path.join(_REPO, "docs", "mem_contracts",
                              "batch_fit.json")


def load_fit_table(path: str | None = None) -> dict | None:
    """The banked batch-fit table, or None when it isn't banked (an
    engine without a table admits everything: a refusal we cannot
    justify numerically is worse than none)."""
    path = path or FIT_TABLE_PATH
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def price_residency(family: str, max_bucket: int,
                    fit_table: dict | None) -> int | None:
    """Predicted resident bytes for one served model at its largest
    bucket, or None when the table has no row for the family (unknown
    => unpriceable => the policy admits)."""
    entry = ((fit_table or {}).get("families", {})
             .get(family, {}).get("f32"))
    if entry is None:
        return None
    train = predicted_bytes(entry["c0"], entry["c1"], max_bucket)
    return max(int(entry.get("params_bytes", 0)),
               train - int(entry.get("slots_bytes", 0)))


class AdmissionPolicy:
    """The load gate: admit/refuse verdicts against the usable-HBM
    budget, shared arithmetic with the queue pre-flight."""

    def __init__(self, fit_table: dict | None = None,
                 hbm_bytes: int = V5E_HBM_BYTES,
                 usable_frac: float = HBM_USABLE_FRAC):
        self.fit_table = fit_table
        self.budget_bytes = int(hbm_bytes * usable_frac)

    def admit(self, family: str, max_bucket: int,
              resident_bytes: int) -> dict:
        """Verdict for loading ``family`` (largest bucket ``max_bucket``)
        next to ``resident_bytes`` of already-loaded models.  ``fits``
        is True for unpriceable families — the gate refuses only what it
        can justify numerically."""
        predicted = price_residency(family, max_bucket, self.fit_table)
        verdict = {
            "family": family,
            "max_bucket": int(max_bucket),
            "predicted_bytes": 0 if predicted is None else predicted,
            "resident_bytes": int(resident_bytes),
            "budget_bytes": self.budget_bytes,
            "priced": predicted is not None,
            "fits": True,
        }
        if predicted is not None:
            verdict["fits"] = \
                resident_bytes + predicted <= self.budget_bytes
        return verdict
