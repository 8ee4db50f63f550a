"""Analytic communication model for the parallel modes.

SparkNet's core claim is a *communication model* (Moritz et al., ICLR
2016, PAPER.md): tau local steps per averaging round trade collective
volume against convergence, so the number and size of collectives per
round IS the design.  This module states that design as checkable
arithmetic — per mode, which collective families the lowered XLA
program must (and must not) contain, and how many bytes per round the
required ones may move — so ``graphcheck`` can assert the compiled
graph against the theory instead of trusting it.

Deliberately stdlib-only (the analysis-package contract: importable
without initializing a backend).  All byte figures come from the caller's
actual variable trees; nothing here touches jax.

The arithmetic, per mode (W = data-axis width, S = param bytes,
T = state bytes):

* ``solo``     — no mesh: ZERO collectives of any kind.
* ``dp``-style — tau=1 sync SGD: GSPMD inserts one grad all-reduce per
  param blob, so total all-reduce bytes ~= S (grads are param-dtype)
  plus the scalar loss pmean and, for BN families, the synced per-batch
  statistics (~ a few x T).  The paper's degenerate tau=1 case —
  per-STEP communication (ref: caffe/src/caffe/parallel.cpp P2PSync).
* ``tau``      — the SparkNet round: tau local steps, then ONE
  weight-sized pmean of params+state (slots stay per-worker) plus the
  scalar loss.  Bytes ~= S + T per ROUND — and crucially none of it
  may sit inside the tau-step loop body, or the program is paying
  per-step sync the tau knob exists to amortize.
* ``easgd``    — elastic round: psum of the param-sized worker-center
  difference + pmean of state; same S + T budget, same no-loop rule.
* ``tp``       — Megatron output-channel sharding: activation
  all-reduces/all-gathers whose volume depends on layer shapes, not on
  S alone — presence of all-reduce is required, bytes are recorded in
  the manifest (drift-pinned) rather than modeled.
* ``sp``       — Ulysses sequence parallelism: heads scatter and
  sequence re-gather are all-to-alls; grad sync still rides 'data'.
* ``gpipe``    — pipeline: ppermute activation hops between stages.
* ``moe``      — expert dispatch: token all-to-all out and back.
"""

from __future__ import annotations

import dataclasses

__all__ = ["CommExpectation", "expected_comm", "COLLECTIVE_KINDS"]

# the five collective families the census distinguishes (HLO op names,
# async -start forms folded in by the census)
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-permute",
)

# Tolerances for the byte-modeled modes.  The lower bound says "the
# full gradient/model really is reduced" (anything less means a blob
# was dropped from the sync); the upper bound leaves room for the
# scalar loss, BN statistics syncs, and XLA's small bookkeeping
# reductions without letting a second copy of the model slip through
# (2x would mean duplicated sync — the exact bug class the manifest
# diff exists to catch).
_LO_FRAC = 0.95
_HI_FRAC = 1.60
_SLACK_BYTES = 65536


@dataclasses.dataclass(frozen=True)
class CommExpectation:
    """What one mode's lowered program may say on the wire.

    ``required`` maps collective kind -> (lo, hi) total-byte window, or
    None for presence-only (volume recorded in the manifest, not
    modeled).  ``forbidden`` kinds must not appear at all.  When
    ``loop_collectives_ok`` is False, no required-kind collective
    moving more than ``loop_bytes_floor`` may sit inside a while-loop
    body — the per-round-not-per-step contract of tau averaging.
    """

    required: dict
    forbidden: tuple
    loop_collectives_ok: bool = True
    loop_bytes_floor: int = 4096
    note: str = ""


def _window(model_bytes: int, state_bytes: int = 0) -> tuple:
    lo = int(_LO_FRAC * model_bytes)
    hi = int(_HI_FRAC * model_bytes + 8 * state_bytes + _SLACK_BYTES)
    return (lo, hi)


def expected_comm(mode: str, *, param_bytes: int,
                  state_bytes: int = 0) -> CommExpectation:
    """The analytic expectation for ``mode`` given the actual model
    sizes.  Raises KeyError for unknown modes — a new parallel mode
    must state its communication contract here before it can bank a
    manifest."""
    # solo_remat shares solo's contract: rematerialization recomputes
    # on-chip, it never creates a wire.  solo_act_bf16 likewise:
    # activation storage narrows on-chip residency, never a wire.
    if mode in ("solo", "solo_nhwc", "solo_remat", "solo_act_bf16"):
        return CommExpectation(
            required={},
            forbidden=COLLECTIVE_KINDS,
            note="single chip: any collective is a lowering bug",
        )
    # the serving engine's AOT bucket forwards (serve/engine.py):
    # single-chip TEST-phase inference — solo's zero-collective contract
    if mode.startswith("serve"):
        return CommExpectation(
            required={},
            forbidden=COLLECTIVE_KINDS,
            note="single-chip AOT serving forward: any collective is a "
                 "lowering bug",
        )
    if mode.startswith("decode"):
        return CommExpectation(
            required={},
            forbidden=COLLECTIVE_KINDS,
            note="single-chip paged/rectangle decode step: any "
                 "collective is a lowering bug",
        )
    # dp_nhwc shares dp's budget exactly: params never reorient under
    # the nhwc layout (ops/layout.py), so the grad all-reduce moves the
    # same bytes — a layout that changed this block would be a bug.
    # dp_remat likewise: recompute changes what the backward reads,
    # not what the mesh reduces.  dp_act_bf16 likewise: bf16 storage
    # narrows saved activations, grads stay f32 param-sized.
    if mode in ("dp", "dp_bf16", "mobilenet_dp", "dp_nhwc", "dp_remat",
                "dp_act_bf16"):
        return CommExpectation(
            required={"all-reduce": _window(param_bytes, state_bytes)},
            forbidden=("all-to-all", "collective-permute", "all-gather"),
            note="tau=1 sync SGD: one grad-sized all-reduce per step; "
                 "an all-gather here means a param got resharded",
        )
    if mode == "tau":
        return CommExpectation(
            required={"all-reduce": _window(param_bytes + state_bytes)},
            forbidden=("all-to-all", "all-gather"),
            loop_collectives_ok=False,
            note="SparkNet round: ONE model-sized pmean per tau steps, "
                 "outside the local-step loop (the paper's tau "
                 "amortization) — slots stay per-worker",
        )
    if mode.startswith("elastic"):
        # width-parameterized (elastic_w8/w6/w4 — parallel/elastic.py):
        # the weighted tau round moves ONE model-sized weighted psum
        # (params+state), one scalar weight-sum psum, and the loss pmean
        # per ROUND, regardless of the mesh width the pool re-formed to
        # — that invariance across W is exactly what the banked twins
        # pin.  Slots stay per-worker, like the tau mode.
        return CommExpectation(
            required={"all-reduce": _window(param_bytes + state_bytes)},
            forbidden=("all-to-all", "all-gather"),
            loop_collectives_ok=False,
            note="elastic tau round: ONE weighted model-sized psum per "
                 "round (+ scalar weight sum), outside the local-step "
                 "loop; contract is width-invariant across mesh "
                 "re-formation",
        )
    if mode == "easgd":
        return CommExpectation(
            required={"all-reduce": _window(param_bytes + state_bytes)},
            forbidden=("all-to-all", "all-gather"),
            loop_collectives_ok=False,
            note="elastic round: param-sized psum of (x_i - center) + "
                 "state pmean, outside the local-step loop",
        )
    if mode == "tp":
        return CommExpectation(
            required={"all-reduce": None},
            forbidden=("all-to-all",),
            note="tensor parallelism: activation partial-sum "
                 "all-reduces (volume is layer-shaped; manifest-pinned)",
        )
    if mode == "sp":
        return CommExpectation(
            required={"all-to-all": None, "all-reduce": None},
            forbidden=(),
            note="Ulysses sequence parallelism: head-scatter/seq-gather "
                 "all-to-alls + the data-axis grad sync",
        )
    if mode == "gpipe":
        return CommExpectation(
            required={"collective-permute": None},
            forbidden=("all-to-all",),
            note="pipeline: ppermute activation hops between stages",
        )
    if mode == "moe":
        return CommExpectation(
            required={"all-to-all": None},
            forbidden=("collective-permute",),
            note="expert parallelism: token all-to-all out and back",
        )
    raise KeyError(
        f"no communication model for mode {mode!r} — add its contract "
        "to sparknet_tpu/analysis/comm_model.py before banking a "
        "manifest")
