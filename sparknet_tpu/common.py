"""Global configuration and PRNG-key discipline.

TPU-native analog of the per-thread ``Caffe`` singleton
(ref: caffe/src/caffe/common.cpp:1-282, common.hpp:107-156): Brew mode,
device selection, seeded RNG, and ``solver_count`` all collapse into a small
immutable config plus explicit ``jax.random`` key threading — there is no
hidden global RNG state on TPU; every stochastic op takes a key derived via
``fold_in`` from (seed, iteration, layer-id).
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
import sys
import tempfile
from typing import Any

import jax
import jax.numpy as jnp

# the chaos-schedule lock instrumentation (SPARKNET_CHAOS_SCHED,
# conccheck leg (c)) — re-exported here as the public surface; the
# implementation stays stdlib-only in _chaoslock.py so serve/batcher.py
# and the analysis package can import it without jax
from sparknet_tpu._chaoslock import (  # noqa: F401
    chaos_armed,
    chaos_seed,
    named_condition,
    named_lock,
    named_rlock,
    observed_edges,
    reset_observed,
)


class Phase(enum.Enum):
    """Network phase (ref: caffe.proto ``enum Phase { TRAIN = 0; TEST = 1; }``)."""

    TRAIN = 0
    TEST = 1


@dataclasses.dataclass(frozen=True)
class Config:
    """Framework-wide numeric / device configuration.

    ``compute_dtype`` is the activation dtype inside jitted programs; on TPU
    bfloat16 keeps matmuls/convs on the MXU at full rate.  Params and
    optimizer state stay in ``param_dtype`` (f32) — the mixed-precision
    scheme XLA fuses casts for.  Tests run f32/f32 on CPU for exact
    numerical gradient checks.

    ``layout`` is the INTERNAL orientation of rank-4 image blobs inside
    jitted programs: ``"nchw"`` (default — Caffe blob order, SURVEY §2.2)
    or ``"nhwc"`` (channels-last, the MXU's preferred orientation; image
    bytes arrive HWC off the wire so the feed link ships its natural
    order with zero entry transpose).  Param blobs are layout-INVARIANT:
    conv weights stay OIHW and fc weights stay (num_output, C·H·W) wire
    order in both layouts, so checkpoints/sharding/PTQ never convert —
    only activations and feed shapes move (``ops/layout.py``).  Like
    every Config field this is read at TRACE time; the ``SPARKNET_LAYOUT``
    env var seeds the default, ``tpunet --layout`` / ``set_config`` flip
    it per run.  NCHW remains the default until the on-chip A/B clears
    the repo's >5% promote rule (docs/BENCHMARKS.md "Layout").
    """

    seed: int = 1  # ref: common.cpp set_random_seed
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    layout: str = os.environ.get("SPARKNET_LAYOUT", "nchw").lower()
    # Host feed architecture: ``"threaded"`` (default — the daemon-thread
    # DevicePrefetcher, bit-identical to the pre-pipeline feed) or
    # ``"process"`` (multi-process shared-memory ring, ``data/pipeline.py``
    # — decode/transform escape the GIL; opt-in until the A/B clears the
    # promote rule).  Like ``layout``, read where feeds are BUILT (the CLI
    # and app loops), not inside jitted programs; ``SPARKNET_FEED`` seeds
    # the default, ``tpunet train --feed`` flips it per run.
    feed: str = os.environ.get("SPARKNET_FEED", "threaded").lower()
    # Rematerialization policy for the train step's forward (the bytes
    # diet the bytecheck schedule search scores chip-free — ROADMAP
    # item 5): ``""`` (default — off, every traced program byte-
    # identical to the banked manifests), ``"full"`` (jax.checkpoint,
    # nothing saveable — the maximal recompute arm), ``"dots"``
    # (dots_saveable — matmul outputs kept, convs recomputed), or
    # ``"blocks"`` (per-block boundaries: pooling-layer outputs tagged
    # ``checkpoint_name`` in compiler/graph.py and saved via
    # save_only_these_names; everything between boundaries recomputed).
    # Routed through ``solvers/solver.py remat_policy`` into the
    # step builder; the banked winner per family lives in
    # ``docs/byte_contracts/remat_policy.json``.  Read at Solver
    # CONSTRUCTION/trace time like every Config field;
    # ``SPARKNET_REMAT`` seeds it, the bench A/B flips it via
    # ``SPARKNET_BENCH_REMAT``.
    remat: str = os.environ.get("SPARKNET_REMAT", "").lower()
    # Activation STORAGE policy for the forward graph (ROADMAP item 5's
    # bf16-storage-with-f32-accumulation lever, scored chip-free by the
    # numcheck mixed-precision search): ``""`` (default — off, every
    # traced program byte-identical to the banked manifests), ``"io"``
    # (feed blobs stored bf16), ``"blocks"`` (pooling-boundary outputs
    # stored bf16 — the same boundaries remat's "blocks" policy saves,
    # so the two compose into "save less, and save it half-width"), or
    # ``"full"`` (every non-loss layer output stored bf16).  Storage
    # only: every layer UPCASTS its inputs to ``compute_dtype`` before
    # compute, so dot/conv/reduce accumulation stays f32 and loss/BN
    # statistics stay pinned f32 (the numcheck contracts).  The banked
    # winner per family lives in ``docs/num_contracts/
    # mixed_policy.json``.  Read at TRACE time like every Config field;
    # ``SPARKNET_ACT_DTYPE`` seeds it ("bf16" aliases to the "blocks"
    # banked-winner shape), the bench A/B flips it via
    # ``SPARKNET_BENCH_ACT_DTYPE``.
    activation_dtype: str = os.environ.get("SPARKNET_ACT_DTYPE", "").lower()
    # Default mesh axis names: data parallelism over 'data', within-layer
    # (tensor) sharding over 'model', sequence/context parallelism over
    # 'seq' (ring / Ulysses attention).
    data_axis: str = "data"
    model_axis: str = "model"
    seq_axis: str = "seq"


# Peak matmul FLOP/s by TPU generation and compute dtype (public specs).
# bf16 columns are the PUBLISHED bf16 peaks — v5e's oft-quoted 394 is its
# int8 TOPS figure, not bf16; f32 ~ bf16/4 (multi-pass MXU emulation —
# there is no native f32 matmul mode).  Single source of truth for every
# MFU/roofline consumer (bench.py, tpunet time --trace, chip_smoke.py),
# read through ``tpu_peak_flops`` so an unlisted device is an error.
TPU_PEAK_FLOPS = {
    # device_kind substring -> {dtype: peak FLOP/s}
    "v5 lite": {"bf16": 197e12, "f32": 49e12},
    "v5e": {"bf16": 197e12, "f32": 49e12},
    "v5p": {"bf16": 459e12, "f32": 115e12},
    "v4": {"bf16": 275e12, "f32": 69e12},
    "v6": {"bf16": 918e12, "f32": 230e12},
}


def tpu_peak_flops(device_kind: str) -> dict:
    """The ``TPU_PEAK_FLOPS`` row for a device as JAX names it
    (``jax.devices()[0].device_kind``).  A device the table does not
    list raises: a utilization against an assumed peak is not a
    measurement."""
    kind = str(device_kind).lower()
    for sub, cols in TPU_PEAK_FLOPS.items():
        if sub in kind:
            return cols
    raise ValueError(
        f"device_kind {device_kind!r} matches no row of "
        f"common.TPU_PEAK_FLOPS ({sorted(TPU_PEAK_FLOPS)}); add its "
        "published peak before reporting a utilization on it")


# v5e HBM bandwidth (public spec), the bytes term of the same rooflines.
V5E_HBM_BYTES_S = 819e9

# Canonical Config.activation_dtype policies and the spellings that
# normalize into them (set_config and compiler/graph.py share these so
# a raw SPARKNET_ACT_DTYPE seed and a set_config call agree).  "bf16"
# aliases to "blocks" — the deterministic shape of the banked winner
# consumers without table access (set_config cannot read
# docs/num_contracts/mixed_policy.json) fall back to; bench.py resolves
# the actual banked policy before seeding.
ACT_POLICIES = ("", "io", "blocks", "full")
ACT_POLICY_ALIASES = {"none": "", "off": "", "f32": "", "float32": "",
                      "bf16": "blocks", "bfloat16": "blocks"}


def act_storage_policy(value: str | None = None) -> str:
    """Normalize an ``activation_dtype`` spelling to its canonical
    policy (default: the current config's), raising on unknowns — the
    single read path for trace-time consumers, so an unvalidated env
    seed can never silently half-apply."""
    raw = get_config().activation_dtype if value is None else value
    ap = ACT_POLICY_ALIASES.get(str(raw).lower(), str(raw).lower())
    if ap not in ACT_POLICIES:
        raise ValueError(f"unknown activation_dtype policy {raw!r} "
                         f"(want one of {ACT_POLICIES} or an alias "
                         f"{tuple(ACT_POLICY_ALIASES)})")
    return ap


_lock = named_lock("common._lock")
_config = Config()


def get_config() -> Config:
    return _config


def set_config(**overrides) -> Config:
    """Replace fields of the global config; returns the new config.

    The config is read at TRACE time: jitted programs (Solver steps,
    trainers) bake in the values seen on their first call and do NOT
    retrace on later ``set_config`` — set ``compute_dtype`` etc. before
    constructing/stepping a Solver, not between steps."""
    global _config
    if "layout" in overrides:
        lay = str(overrides["layout"]).lower()
        if lay not in ("nchw", "nhwc"):
            raise ValueError(f"layout must be 'nchw' or 'nhwc', got "
                             f"{overrides['layout']!r}")
        overrides = {**overrides, "layout": lay}
    if "feed" in overrides:
        feed = str(overrides["feed"]).lower()
        if feed not in ("threaded", "process"):
            raise ValueError(f"feed must be 'threaded' or 'process', got "
                             f"{overrides['feed']!r}")
        overrides = {**overrides, "feed": feed}
    if "remat" in overrides:
        rp = str(overrides["remat"]).lower()
        rp = {"none": "", "off": ""}.get(rp, rp)
        if rp not in ("", "full", "dots", "blocks"):
            raise ValueError(
                f"remat must be one of '', 'full', 'dots', 'blocks', got "
                f"{overrides['remat']!r}")
        overrides = {**overrides, "remat": rp}
    if "activation_dtype" in overrides:
        ap = str(overrides["activation_dtype"]).lower()
        ap = ACT_POLICY_ALIASES.get(ap, ap)
        if ap not in ACT_POLICIES:
            raise ValueError(
                f"activation_dtype must be one of '', 'io', 'blocks', "
                f"'full' (or an alias: none/off/f32/float32 -> '', "
                f"bf16/bfloat16 -> 'blocks'), got "
                f"{overrides['activation_dtype']!r}")
        overrides = {**overrides, "activation_dtype": ap}
    with _lock:
        _config = dataclasses.replace(_config, **overrides)
    return _config


def force_platform(name: str) -> None:
    """Pin jax to a platform for this process (same effect as launching
    with ``JAX_PLATFORMS=<name>``).  Must run before the first
    backend-initializing jax call."""
    jax.config.update("jax_platforms", name)


def device_stamp() -> dict:
    """Where this process runs, as jax reports it — rides every record a
    measuring entry point prints."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_chip(tool: str) -> dict:
    """The :func:`device_stamp` of a measuring tool, or ``SystemExit(2)``
    with nothing on stdout when it was asked for a chip and found none:
    jax falls back to the CPU by itself, and a number from that run
    must not exist.  A process TOLD to use the CPU (``JAX_PLATFORMS=cpu``
    or :func:`force_platform`) passes — a rehearsal, which the stamp
    lets every record say."""
    stamp = device_stamp()
    pinned = (os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
              or jax.config.jax_platforms == "cpu")
    if stamp["platform"] == "cpu" and not pinned:
        print(f"{tool}: no accelerator found (jax.devices()[0].platform is "
              "'cpu'); run on the chip, or pin JAX_PLATFORMS=cpu for a "
              "rehearsal", file=sys.stderr)
        raise SystemExit(2)
    return stamp


# the ``jax.named_scope`` names a trace reader may look for in this
# program's executables (compiler/graph.py, solvers/solver.py,
# data/device_transform.py, ops/moe.py, ops/attention.py,
# ops/linear_attention.py): part of the
# compile-cache key.  Add a scope, add it here
CACHE_SCOPES = ("scopes:L.<layer>,S.update,S.augment,"
                "M.route,M.dispatch,M.experts,M.combine,M.shared,"
                "A.core,A.latent,A.rope,A.gate,R.scan,R.gate,D.delta,"
                "LOOP.<region>")


def enable_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    Called once by each entry point (``cli.main``, ``bench.py``,
    ``chip_smoke.py``) — never at package import, never from the test
    harness.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    reads it and nothing is set in code; otherwise the cache lives at
    the FIXED path ``<checkout>/.jax_cache``, so a second process run
    from the same checkout finds what the first compiled.

    The cache key also names the program's device scopes
    (``CACHE_SCOPES``).  A cached executable carries the scope names of
    the source that compiled it, a trace reader finds device time by
    them, and jax strips them from its key: without this a checkout
    whose scopes differ (an older commit beside this one on a shared
    cache) would be served this one's executables, and this one theirs.
    ``jax._src.cache_key.custom_hook`` is jax's own door for such an
    addition; it is private and imported plainly, so a jax upgrade that
    moves it fails here instead of silently mixing executables."""
    from jax._src import cache_key

    cache_key.custom_hook = lambda: CACHE_SCOPES
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def root_key(seed: int | None = None) -> jax.Array:
    """The root PRNG key for a run (ref: common.cpp:set_random_seed)."""
    cfg = get_config()
    return jax.random.key(cfg.seed if seed is None else seed)


def step_key(key: jax.Array, step: jax.Array | int) -> jax.Array:
    """Derive the per-iteration key — jit-safe (``step`` may be traced)."""
    return jax.random.fold_in(key, step)


def layer_key(key: jax.Array, layer_index: int) -> jax.Array:
    """Derive a per-layer key from a step key (static layer index)."""
    return jax.random.fold_in(key, layer_index)


def value_fence(out, max_leaf_elems: int = 65536) -> float:
    """Execution fence for timing loops: fetch the VALUE of the last leaf
    of ``out`` with a device-to-host copy of that buffer.

    ``jax.block_until_ready`` on the step's outputs fences just as well;
    fetching the loss fences AND hands the caller the number it wants to
    record, which is why the timing loops use this.

    Caller contract: ``out`` must be the output of ONE jitted program,
    and its LAST pytree leaf must be a scalar (or tiny array) with data
    dependence on the full computation — the loss, per
    ``jitted_train_step``'s ``(variables, slots, loss)`` ordering.  A
    tuple assembled from separate dispatches only fences the program
    that produced the last leaf; leaves above ``max_leaf_elems`` raise
    rather than silently time a multi-MB device-to-host copy.
    """
    import numpy as np

    leaf = jax.tree_util.tree_leaves(out)[-1]
    size = getattr(leaf, "size", 1)
    if size > max_leaf_elems:
        raise ValueError(
            f"value_fence: last leaf has {size} elements; arrange the "
            "fenced output so its last leaf is the scalar loss (fetching "
            "this array would add a large device-to-host copy inside the "
            "timed region)")
    return float(np.asarray(leaf).ravel()[-1])


def bank_path(path: str, *, measured: bool) -> str:
    """Where a ``bank_guard`` payload actually lands.

    Measured (on-chip) evidence keeps its banked location; unmeasured
    runs — CPU rehearsals, plumbing checks — divert OUTSIDE docs/
    entirely, to ``/tmp/<name>_rehearsal.json``, so a stray smoke run
    can never overwrite chip evidence.
    Idempotent: an already-diverted path is returned unchanged.
    """
    if measured:
        return path
    root, ext = os.path.splitext(os.path.basename(path))
    if root.endswith("_rehearsal"):
        return path
    return os.path.join(tempfile.gettempdir(), f"{root}_rehearsal{ext}")


# Observers notified after every successful bank_guard write — the obs
# Recorder (sparknet_tpu/obs) registers here so banked evidence and the
# runtime journal share ONE code path for ``measured`` stamping.
_BANK_OBSERVERS: list = []


def add_bank_observer(fn) -> None:
    """Register ``fn(path, payload, measured)`` to run after each
    successful :func:`bank_guard` write (idempotent per callable).
    Observer exceptions are contained: banking outranks journaling."""
    if fn not in _BANK_OBSERVERS:
        _BANK_OBSERVERS.append(fn)


def remove_bank_observer(fn) -> None:
    """Deregister a bank observer (no-op if absent)."""
    try:
        _BANK_OBSERVERS.remove(fn)
    except ValueError:
        pass


def bank_guard(path: str, payload, *, measured: bool) -> str | None:
    """The one blessed sink for evidence-file writes (JSON, atomic).

    Every write to a banked-evidence path (``docs/*_last*.json``) must
    flow through here — the
    ``bank-guard`` lint rule (``python -m sparknet_tpu.analysis``) flags
    direct ``open``-for-write on those paths.  Behavior:

    * ``measured=True``: temp-file + atomic ``os.replace`` to ``path``
      (a kill mid-write must never leave a torn file).
    * ``measured=False``: divert to ``bank_path(...)`` under /tmp and
      stamp dict payloads ``{"rehearsal": true}`` so the record cannot
      later be mistaken for chip evidence.

    Returns the path written, or None on OSError (logged to stderr;
    a read-only checkout must not kill the run — stdout remains the
    record).
    """
    path = bank_path(path, measured=measured)
    if not measured and isinstance(payload, dict):
        payload = dict(payload)
        payload["rehearsal"] = True
        payload.setdefault("note", "unmeasured run — not chip evidence")
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    except OSError as e:
        print(f"bank_guard: could not write {path}: {e}", file=sys.stderr)
        return None
    for observer in list(_BANK_OBSERVERS):
        try:
            observer(path, payload, measured)
        except Exception as e:
            print(f"bank_guard: observer failed: {e!r}", file=sys.stderr)
    return path
