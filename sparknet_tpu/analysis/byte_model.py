"""Analytic per-step HBM traffic model for the parallel modes.

SparkNet's own thesis is that bandwidth is the scaling bottleneck —
tau-averaging exists to amortize sync BYTES, not sync flops (Moritz et
al., ICLR 2016, PAPER.md).  This module states that byte bill as
checkable arithmetic, the fifth analysis surface beside source
(graftlint), wire (graphcheck/comm_model), memory (memcheck/mem_model)
and host-plane concurrency (conccheck): per train step, where every
HBM byte goes — params read and written, grads, optimizer slots,
activations saved for the backward out of the jaxpr liveness walk,
collective bytes from ``comm_model``, feed wire bytes — so the ``bytes`` engine can audit the lowered programs against
the model with zero chip time, and the remat schedule search can price
candidate ``jax.checkpoint`` policies BEFORE any of them burns chip
time (the TensorFlow line of work's memory/recompute scheduling as a
static cost model, PAPERS.md).

Deliberately stdlib-only (the analysis-package contract: importable
without initializing a backend).  The jax-touching extraction — tracing a
mode, walking its jaxpr into a ``MemProgram`` — lives in ``bytecheck``
(reusing memcheck's extractor); this module only defines the
arithmetic over the extracted program.

Two estimators of the step's byte bill, deliberately at different
levels:

* the **gross census** (``gross_traffic``): every eqn's operand reads
  plus result writes, summed over the extracted jaxpr — the pre-fusion
  analog of XLA HloCostAnalysis' "bytes accessed" (bench.py reads that
  through ``xla_cost_step_bytes`` below).  Like HloCostAnalysis, a scan/while
  BODY is counted once, independent of trip count.  Fusion makes the
  physical traffic lower than either census; the two agree only within
  a window, which is exactly what the headline reconciliation gate
  states and checks;
* the **class-model floor** (``step_traffic``): the per-op-class bill
  a perfectly-fused backend still pays — each param byte read for
  forward and backward and written once by the update, each grad byte
  written and read once, each optimizer-slot byte read+written, each
  saved-activation byte written by forward and read by backward, the
  collective's wire bytes, the feed's ingest bytes.  The floor is what
  the remat search scores: rematerialization trades saved-activation
  bytes against extra forward param reads, and the floor prices both
  sides of that trade.

The floor must never exceed the gross census for the same program
(``byte-floor-exceeds-census``) — the invariant that keeps the two
estimators honest against each other.
"""

from __future__ import annotations

__all__ = [
    "REMAT_POLICIES",
    "REMAT_RECOMPUTE_PASSES",
    "REMAT_RECOMPUTE_ORDER",
    "HEADLINE_RATIO_WINDOW",
    "HEADLINE_DROP_FLOOR",
    "gbytes",
    "xla_cost_step_bytes",
    "gross_traffic",
    "step_traffic",
    "reconcile",
    "selected_policy",
    "monotonicity_violations",
]

# The remat design space the schedule search enumerates — the
# ``jax.checkpoint`` variants ``Config.remat`` routes through
# solvers/solver.py apply_remat: "none" saves everything jax's default
# VJP saves (policy off), "dots" saves matmul/conv outputs only
# (checkpoint_policies.dots_saveable), "blocks" saves the network's
# block boundaries only (checkpoint_name-tagged pooling outputs,
# compiler/graph.py BLOCK_SAVE_NAME), "full" saves nothing
# (plain jax.checkpoint — everything recomputes in the backward).
REMAT_POLICIES = ("none", "dots", "blocks", "full")

# Extra full-network forward passes the backward pays under each
# policy: any checkpointing variant replays the forward once while
# differentiating (jax.checkpoint's recursive structure collapses to
# one replay for a single top-level checkpoint), so the floor charges
# one extra param-read pass — the byte-side price of the activation
# savings.
REMAT_RECOMPUTE_PASSES = {"none": 0, "dots": 1, "blocks": 1, "full": 1}

# The partial recompute order: (a, b) means b recomputes at least as
# much as a, so b may never SAVE more activation bytes than a
# (more recompute => never more saved bytes — the monotonicity the
# search banks and the tests pin).  "dots" and "blocks" are
# incomparable with each other (different save sets), both sit between
# "none" and "full".
REMAT_RECOMPUTE_ORDER = (
    ("none", "dots"),
    ("none", "blocks"),
    ("dots", "full"),
    ("blocks", "full"),
)

# Gross-census vs measured "bytes accessed" tolerance for the headline
# config (alexnet b256 bf16 solo).  Both figures are operand censuses
# of the same program, but at different IRs: the jaxpr census sees the
# program BEFORE XLA — every mixed-precision cast's read+write, every
# broadcast operand at full size — while HloCostAnalysis prices the
# post-optimization HLO, after algebraic simplification and CSE have
# eliminated much of that traffic (the jaxpr side roughly doubles the
# bf16 program's bill through materialized casts).  The window bounds
# that known, explained gap with margin on both sides — anything
# outside it means one side is describing a different program (a unit
# error, a dropped backward, a trip-count-scaled scan).
HEADLINE_RATIO_WINDOW = (0.85, 2.60)

# The acceptance bar for the schedule search: the selected policy must
# drop the headline family's modeled step bytes by at least this
# fraction vs "none" (ISSUE 17's >= 25%).
HEADLINE_DROP_FLOOR = 0.25


def gbytes(b: float) -> float:
    """Canonical GB rounding for step-traffic figures — the single
    rounding every consumer (bench.py step_gbytes, the manifests, the
    docs tables) shares, so two renderings of one number can never
    disagree in the second decimal."""
    return round(float(b) / 1e9, 2)


def xla_cost_step_bytes(cost) -> float:
    """Extract "bytes accessed" from a ``compiled.cost_analysis()``
    result — the measured side of every reconciliation.  Tolerates the
    older list-of-dict return shape and absent keys (0.0: the caller's
    own no-evidence path).  bench.py and the CLI's ``time --hlo`` both
    route through here: one extraction, one rounding (``gbytes``), one
    source of truth for what "step bytes" means."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not cost:
        return 0.0
    return float(cost.get("bytes accessed", 0.0))


# -------------------------------------------------------------------------
# The gross census (over memcheck's extracted MemProgram)
# -------------------------------------------------------------------------


def gross_traffic(prog) -> int:
    """Total operand-read + result-write bytes over every eqn of an
    extracted ``MemProgram`` — the jaxpr-level analog of XLA
    HloCostAnalysis' "bytes accessed".  Scan/while bodies are counted
    once (memcheck's extractor lists control-flow ops as single eqns),
    matching the HloCostAnalysis convention the banked 12.33 GB/step
    headline figure uses (bench.py's scan note).  Buffer sizes are the
    extractor's per-device figures, so under GSPMD this is per-chip
    traffic."""
    total = 0
    for eqn in prog.eqns:
        total += sum(prog.sizes[r] for r in eqn.reads)
        total += sum(prog.sizes[w] for w in eqn.writes)
    return total


# -------------------------------------------------------------------------
# The class-model floor
# -------------------------------------------------------------------------


def step_traffic(*, param_bytes: int, state_bytes: int = 0,
                 slot_bytes: int = 0, saved_activation_bytes: int = 0,
                 collective_bytes: int = 0, feed_bytes: int = 0,
                 extra_carry_bytes: int = 0, train: bool = True,
                 recompute_passes: int = 0) -> dict:
    """The per-op-class HBM bill of one step (per device), as a
    component breakdown plus total.

    Train accounting (S = param bytes): params are read by the forward,
    read again by the backward, re-read once per recompute pass, and
    written once by the update; grads are written by the backward and
    read by the update; optimizer slots and network state are
    read+written by the update; the saved activations are written by
    the forward and read by the backward; collective and feed bytes
    ride on top.  Forward-only programs (serve/gpipe/moe) read params
    once and pay none of the update-side terms.
    """
    S = int(param_bytes)
    if train:
        comp = {
            "params_read_bytes": (2 + int(recompute_passes)) * S,
            "params_write_bytes": S,
            "grad_bytes": 2 * S,
            "slot_bytes": 2 * int(slot_bytes),
            "state_bytes": 2 * int(state_bytes),
            "extra_carry_bytes": 2 * int(extra_carry_bytes),
            "saved_activation_bytes": 2 * int(saved_activation_bytes),
        }
    else:
        comp = {
            "params_read_bytes": S,
            "params_write_bytes": 0,
            "grad_bytes": 0,
            "slot_bytes": 0,
            "state_bytes": 2 * int(state_bytes),
            "extra_carry_bytes": 0,
            "saved_activation_bytes": 2 * int(saved_activation_bytes),
        }
    comp["collective_bytes"] = int(collective_bytes)
    comp["feed_bytes"] = int(feed_bytes)
    comp["total_bytes"] = sum(comp.values())
    return comp


def reconcile(measured_bytes: float, census_bytes: int,
              window: tuple = HEADLINE_RATIO_WINDOW) -> dict:
    """census/measured ratio vs the stated tolerance window — the
    headline reconciliation verdict (the gate that turns the
    BENCHMARKS.md "bytes-bound" sentence into a machine-checked
    contract)."""
    ratio = census_bytes / measured_bytes if measured_bytes else 0.0
    lo, hi = window
    return {
        "measured_bytes": float(measured_bytes),
        "measured_gbytes": gbytes(measured_bytes),
        "census_bytes": int(census_bytes),
        "census_gbytes": gbytes(census_bytes),
        "ratio": round(ratio, 3),
        "window": [lo, hi],
        "within": bool(lo <= ratio <= hi),
    }


# -------------------------------------------------------------------------
# The banked remat-policy table
# -------------------------------------------------------------------------


def selected_policy(table: dict, family: str, dtype: str,
                    default: str = "full") -> str:
    """The banked bytes-minimal policy for (family, dtype) out of a
    ``docs/byte_contracts/remat_policy.json`` table; ``default`` when
    the table predates the family or carries an unknown policy name
    (a fresh clone's first bank — the remat twins need a deterministic
    answer before the search has ever run)."""
    try:
        pol = table["selected"][family][dtype]["policy"]
    except (KeyError, TypeError):
        return default
    return pol if pol in REMAT_POLICIES else default


def monotonicity_violations(saved_by_policy: dict) -> list:
    """Pairs of ``REMAT_RECOMPUTE_ORDER`` a score table breaks: for
    (a, b) with b the heavier-recompute policy, b saving MORE
    activation bytes than a is a modeling bug (more recompute can only
    shrink the save set).  ``saved_by_policy`` maps policy name ->
    saved-activation bytes; absent policies are skipped."""
    out = []
    for a, b in REMAT_RECOMPUTE_ORDER:
        if a in saved_by_policy and b in saved_by_policy:
            if saved_by_policy[b] > saved_by_policy[a]:
                out.append((a, b))
    return out
