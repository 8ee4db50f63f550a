"""Analytic HBM/VMEM memory model for the parallel modes.

SparkNet's economics are about making scarce accelerator time go
further (Moritz et al., ICLR 2016, PAPER.md).  A job that would OOM on
the chip burns its chip time for nothing, so memory joins comm
(``comm_model.py``) as a statically checkable budget: this module states how many bytes a train
step may hold resident, as arithmetic the ``memcheck`` engine can
evaluate with zero chip time — the same before-hardware cost-modeling
discipline the XLA/GSPMD line of work applies (PAPERS.md).

Deliberately stdlib-only (the analysis-package contract: importable
without initializing a backend).  The jax-touching extraction — jaxpr
walking, ``compiled.memory_analysis()`` — lives in ``memcheck``; this
module only defines the program representation, the liveness
arithmetic, and the batch-fit solver.

The model, per mode (per device):

    peak = max_t  sum(bytes of buffers live at t)

with inputs live from entry (donated ones die at their last use —
credited only when the lowering actually established aliasing),
outputs live to exit, and intermediates live from definition to last
use.  Two estimators of the same quantity must agree:

* the **analytic** walk over the traced jaxpr (this module), and
* **XLA's own buffer assignment** (``compiled.memory_analysis()``:
  ``argument + output + temp - alias`` on the same CPU-mesh lowering
  graphcheck performs).

They are genuinely independent — one sees the program before the
compiler, one after — so exact agreement is impossible by design: the
analytic walk models TPU-style fusion (elementwise chains do not
materialize between layer boundaries), while the CPU cross-check
backend materializes im2col patch buffers for convolutions and reuses
loop-body buffers the walk keeps live.  The contract is therefore
two-sided:

* **residency** (arguments + outputs - donated aliasing) must match
  within ``RESIDENCY_TOL_BYTES`` — both sides count the same physical
  buffers, so a mismatch means the donation/sharding accounting is
  wrong (exactly the class that silently doubles params+slots);
* **peak** must agree within ``PEAK_RATIO_WINDOW`` — an order-of-
  magnitude gate that catches unit errors, dropped carries, and
  double-counted models, while the per-mode ratio itself is banked in
  ``docs/mem_contracts/`` and drift-pinned, so any movement is a
  finding even inside the window.
"""

from __future__ import annotations

import dataclasses

__all__ = [
    "V5E_HBM_BYTES",
    "V5E_VMEM_BYTES",
    "VMEM_PLANNING_BYTES",
    "HBM_USABLE_FRAC",
    "PEAK_RATIO_WINDOW",
    "RESIDENCY_TOL_BYTES",
    "MemEqn",
    "MemProgram",
    "peak_residency",
    "affine_fit",
    "predicted_bytes",
    "max_fit_batch",
    "MODE_DIVISORS",
    "mode_footprint",
]

# -- the v5e budget constants (single source for every consumer) ----------
#
# HBM: 16 GiB per v5e chip (public spec; same table as common.
# TPU_PEAK_FLOPS / V5E_HBM_BYTES_S — spelled here too so this module
# stays importable without jax-adjacent modules).  XLA reserves a slice
# for its own runtime scratch, so admission budgets HBM_USABLE_FRAC of
# it — a load predicted past that line would compile into an allocator
# failure at run time.
V5E_HBM_BYTES = 16 * 2**30
HBM_USABLE_FRAC = 0.90

# VMEM: 128 MiB physical per v5e core (the r5 on-chip A/B sweeps the
# scoped limit up to 96 MiB via xla_tpu_scoped_vmem_limit_kib, so the
# ceiling is real); the accelerator guide's planning figure is ~16 MB
# per core (/opt/skills/guides/pallas_guide.md "VMEM ~16 MB/core") —
# kernels are checked against the hard cap and their headroom vs the
# conservative planning figure is banked in the manifest.
V5E_VMEM_BYTES = 128 * 2**20
VMEM_PLANNING_BYTES = 16 * 2**20

# -- the documented estimator tolerance -----------------------------------
#
# Residency: both estimators count the same arg/output buffers; the
# slack covers XLA's tuple/token bookkeeping (a few hundred bytes
# observed) with margin, NOT a second model copy — the smallest real
# accounting bug (an undonated bias blob) is kilobytes.
RESIDENCY_TOL_BYTES = 65536

# Peak: analytic/XLA ratio window.  Observed across the 13 banked
# modes: 0.23 (mobilenet_dp — the CPU backend's grouped/depthwise-conv
# scratch exceeds the generic im2col term the cross-check models) to
# ~2.8 (moe/sp — shard_map bodies whose loop buffers XLA reuses but
# the walk keeps).  The window bounds those known, explained
# divergences with margin; anything outside it is a modeling or
# lowering bug, and inside it the banked per-mode ratio still
# drift-pins the exact value (docs/mem_contracts/<mode>.json
# "peak_ratio").
PEAK_RATIO_WINDOW = (0.18, 4.0)


# -------------------------------------------------------------------------
# Program representation + liveness walk
# -------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MemEqn:
    """One program step: reads/writes name buffers in ``MemProgram.sizes``.

    ``extra`` is transient residency attributed to the step itself (a
    scan/while body's internal peak — the carry/remat bytes the issue
    of record calls out); ``scratch`` is backend materialization the
    CROSS-CHECK side must model but the TPU-facing estimate must not
    (the CPU backend's im2col conv patches).
    """

    reads: tuple
    writes: tuple
    extra: int = 0
    scratch: int = 0


@dataclasses.dataclass
class MemProgram:
    """A traced step, reduced to what the liveness walk needs.

    ``sizes`` maps buffer name -> PER-DEVICE bytes (the extractor
    resolves global avals through the actual shardings before anything
    reaches this module).  ``donated`` holds input names whose aliasing
    the lowering actually established — donation claimed in source but
    dropped by jit is NOT credited, which is the point.
    """

    eqns: list
    sizes: dict
    inputs: list
    outputs: list
    donated: frozenset = frozenset()

    def input_bytes(self) -> int:
        return sum(self.sizes[n] for n in set(self.inputs))

    def output_bytes(self) -> int:
        return sum(self.sizes[n] for n in set(self.outputs))

    def donated_bytes(self) -> int:
        return sum(self.sizes[n] for n in self.donated)


def peak_residency(prog: MemProgram, xcheck: bool = False) -> dict:
    """Walk ``prog`` once, tracking the live set.

    Inputs start live; a donated input dies after its last read (its
    buffer is reused — the donation credit), a non-donated one never
    dies (the caller still owns it).  Every write goes live at its eqn
    and dies after its last read unless it is a program output.  The
    returned ``peak_bytes`` subtracts ``donated_bytes`` once: a donated
    buffer and the output aliasing it are one allocation, and the walk
    would otherwise count both at the handover eqn.

    ``xcheck=True`` adds each eqn's backend ``scratch`` term — the
    CPU-cross-check view; the default is the TPU-facing estimate.
    """
    inf = float("inf")
    last: dict = {}
    for name in prog.inputs:
        last[name] = -1 if name in prog.donated else inf
    for i, eqn in enumerate(prog.eqns):
        for r in eqn.reads:
            if last.get(r) != inf:
                last[r] = i
    for name in prog.outputs:
        last[name] = inf

    live = set(prog.inputs)
    cur = sum(prog.sizes[n] for n in live)
    peak, peak_at = cur, -1
    for i, eqn in enumerate(prog.eqns):
        for w in eqn.writes:
            if w not in live:
                live.add(w)
                cur += prog.sizes[w]
        here = cur + eqn.extra + (eqn.scratch if xcheck else 0)
        if here > peak:
            peak, peak_at = here, i
        for n in [n for n in live if last.get(n, i) <= i]:
            live.remove(n)
            cur -= prog.sizes[n]
    donated = prog.donated_bytes()
    residency = prog.input_bytes() + prog.output_bytes() - donated
    return {
        "peak_bytes": max(peak - donated, residency),
        "residency_bytes": residency,
        "temp_bytes": max(0, peak - donated - residency),
        "peak_at_eqn": peak_at,
    }


# -------------------------------------------------------------------------
# Batch-fit arithmetic
# -------------------------------------------------------------------------


def affine_fit(b1: int, y1: int, b2: int, y2: int) -> tuple:
    """(c0, c1) with y = c0 + c1*b through two probe points.  Activation
    bytes are linear in batch by construction (every feed/blob carries
    the batch on a leading axis), so two abstract traces pin the whole
    family — no per-candidate-batch retracing."""
    if b2 == b1:
        raise ValueError("affine_fit needs two distinct probe batches")
    c1 = (y2 - y1) / float(b2 - b1)
    return y1 - c1 * b1, c1


def predicted_bytes(c0: float, c1: float, batch: int) -> int:
    return int(c0 + c1 * batch)


def max_fit_batch(c0: float, c1: float, budget_bytes: int,
                  multiple: int = 8) -> int:
    """Largest batch (rounded down to ``multiple``) whose predicted
    footprint fits the budget; 0 when even the constant term does not
    fit.  Monotone in budget and anti-monotone in c0/c1 by
    construction — the property the fit tests pin."""
    if c1 <= 0:
        return 0 if c0 > budget_bytes else multiple * (2**20)  # unbounded
    b = int((budget_bytes - c0) / c1)
    return max(0, (b // multiple) * multiple)


# Per-device divisors for the parallel modes, derived from
# parallel/sharding.py's layout rules.  ``batch_div`` divides the
# activation (c1) term: DP/SP shard the batch/sequence axis W ways.
# ``param_div`` divides params+slots: TP shards the output-channel axis
# of blobs clearing min_tp_dim (the effective divisor is computed per
# blob by memcheck via sharding.blob_shard_degree — the table entry is
# the mesh axis it divides by); gpipe places 1/S of the stages per
# device but holds every microbatch's activations until backward, so
# its activation term is NOT divided (the GPipe schedule's known
# memory shape).
MODE_DIVISORS = {
    "solo": {"batch_div": 1, "param_div": 1,
             "note": "single chip: the bench.py shape"},
    "dp": {"batch_div": "data", "param_div": 1,
           "note": "params replicate, batch shards over the data axis"},
    "tp": {"batch_div": 1, "param_div": "model",
           "note": "Megatron output-channel sharding: per-blob divisor "
                   "from sharding.blob_shard_degree (min_tp_dim floor)"},
    "sp": {"batch_div": "seq", "param_div": 1,
           "note": "Ulysses sequence parallelism: the sequence axis of "
                   "activations shards; params replicate"},
    "gpipe": {"batch_div": 1, "param_div": "stage",
              "note": "pipeline: 1/S of the stages per device, but GPipe "
                      "holds all microbatch activations until backward — "
                      "activation term undivided (conservative)"},
}


def mode_footprint(entry: dict, mode: str, batch: int,
                   axis_sizes: dict | None = None) -> int:
    """Per-device predicted bytes for a banked fit-table ``entry`` at
    ``batch`` under ``mode``.  ``entry`` carries c0/c1 plus the param
    split (params_slots_bytes, tp_params_slots_bytes) banked by the fit
    solver; ``axis_sizes`` maps mesh axis name -> width (default 8 data,
    2 model, 4 seq, 8 stage — the virtual-mesh shapes the manifests
    use)."""
    axes = {"data": 8, "model": 2, "seq": 4, "stage": 8}
    axes.update(axis_sizes or {})
    div = MODE_DIVISORS[mode]
    c0, c1 = entry["c0"], entry["c1"]
    ps = entry.get("params_slots_bytes", 0)
    bdiv = axes.get(div["batch_div"], 1) if isinstance(div["batch_div"], str) \
        else div["batch_div"]
    act = c1 * batch / max(1, bdiv)
    const = c0
    if div["param_div"] == "model":
        const = c0 - ps + entry.get("tp_params_slots_bytes", ps)
    elif div["param_div"] == "stage":
        const = c0 - ps + ps / axes["stage"]
    return int(const + act)
