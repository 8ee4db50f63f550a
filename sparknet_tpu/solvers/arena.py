"""Flat param/slot arenas for the one-pass fused optimizer update.

The blob-wise optimizer state (``solvers/updates.py``: one history list
per param blob, the Caffe ``SGDSolver::history_`` shape, ref:
sgd_solver.cpp PresolveHistory) re-streams params+slots through HBM
once per elementwise op of the update chain.  This module re-layouts
that state for the fused sweep (``ops/pallas_kernels.fused_update``):
params, grads, and each slot history are viewed as ONE contiguous flat
arena per role, built once at Solver construction with an index map
back to blobs — Caffe's own ``Blob`` contiguity taken to its limit (the
reference's JNA weight wire is a single flat float buffer per blob,
ref: Net.scala:131-171; here the whole MODEL is one buffer per role).

Layout invariants:

* every blob is padded to a multiple of the kernel tile
  (``pallas_kernels.ARENA_TILE``), so a tile never spans two blobs and
  the kernel applies per-blob lr_mult/decay_mult via a per-TILE segment
  table (scalar prefetch) without ever branching per element;
* pad elements are zero in every arena and STAY zero under all six
  rules (zero grad, zero param — the update fixed point), so arena
  reductions (the global-norm clip) equal their blob-wise twins;
* the index map is pure geometry (offset/size/shape/dtype per blob):
  checkpoints stay blob-wise — ``pack``/``unpack`` round-trip through
  it, so a snapshot taken mid-fused-run restores into an unfused
  solver (and vice versa), layout- and storage-dtype-invariant;
* arenas may be stored bf16 (``Config.storage_dtype``) while blobs and
  checkpoints keep their param dtype; the kernel computes in f32
  registers either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.ops.pallas_kernels import (
    ARENA_TILE,
    FUSED_RULE_SLOTS,
    UpdateStatics,
    fused_update,
)

__all__ = [
    "ArenaEntry",
    "ArenaLayout",
    "build_layout",
    "pack",
    "unpack",
    "pack_slots",
    "unpack_slots",
    "init_slot_arenas",
    "arena_apply_update",
    "update_statics",
]


@dataclasses.dataclass(frozen=True)
class ArenaEntry:
    """One blob's span in the flat arenas (the index-map row)."""

    lname: str
    index: int  # blob position within the layer's param list
    shape: tuple
    dtype: str  # the BLOB dtype (unpack casts back to it)
    offset: int  # element offset of the blob's span
    size: int  # true element count
    span: int  # padded element count (multiple of the tile)


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Geometry + per-tile segment tables, built once per solver.

    ``struct`` records the FULL params-tree shape (layer -> blob count,
    including zero-param layers) so unpack reproduces the exact pytree
    structure the jitted carry contract requires.  ``tile_lr`` /
    ``tile_decay`` are the scalar-prefetch segment tables: lr_mult and
    folded ``weight_decay * decay_mult`` per tile (pad tiles inherit
    their blob's values — pad elements are zero, so the values are
    inert there)."""

    entries: tuple
    struct: tuple  # ((lname, n_blobs), ...) in params-dict order
    tile: int
    total: int  # padded total elements (n_tiles * tile)
    n_tiles: int
    rule: str
    n_slots: int
    storage_dtype: str  # "f32" | "bf16"
    tile_lr: Any  # np.ndarray [n_tiles] f32
    tile_decay: Any  # np.ndarray [n_tiles] f32

    @property
    def storage(self):
        return jnp.bfloat16 if self.storage_dtype == "bf16" else jnp.float32

    @property
    def itemsize(self) -> int:
        return 2 if self.storage_dtype == "bf16" else 4

    @property
    def total_bytes(self) -> int:
        return self.total * self.itemsize

    def param_bytes(self) -> int:
        """True (unpadded) param bytes at the storage dtype."""
        return sum(e.size for e in self.entries) * self.itemsize

    def padded_frac(self) -> float:
        true = sum(e.size for e in self.entries)
        return self.total / max(1, true)

    def index_map(self) -> list:
        """The serializable blob <-> arena map (docs/tests; the
        checkpoint round-trip is pack/unpack THROUGH this geometry)."""
        return [
            {"layer": e.lname, "blob": e.index, "offset": e.offset,
             "size": e.size, "span": e.span, "shape": list(e.shape),
             "dtype": e.dtype}
            for e in self.entries
        ]


def build_layout(params, specs, cfg, *, storage_dtype: str | None = None,
                 tile: int = ARENA_TILE) -> ArenaLayout:
    """Build the arena geometry from a params tree (concrete arrays or
    ShapeDtypeStructs — only .shape/.dtype are read) + the per-blob
    ParamSpecs + a SolverConfig.  Iteration order is the params dict's
    own (layer creation) order, the same order ``updates.apply_update``
    walks — the index map IS that order made explicit."""
    if storage_dtype is None:
        from sparknet_tpu.common import get_config

        storage_dtype = get_config().storage_dtype
    entries: list = []
    struct: list = []
    lr_spans: list = []  # (n_tiles_of_blob, lr_mult, folded_decay)
    offset = 0
    for lname, plist in params.items():
        struct.append((lname, len(plist)))
        for i, p in enumerate(plist):
            size = int(np.prod(p.shape))  # () -> 1; any zero dim -> 0
            span = -(-size // tile) * tile if size else 0
            spec = specs[lname][i]
            entries.append(ArenaEntry(
                lname=lname, index=i, shape=tuple(p.shape),
                dtype=jnp.dtype(p.dtype).name, offset=offset, size=size,
                span=span))
            lr_spans.append((span // tile, float(spec.lr_mult),
                             float(cfg.weight_decay) * float(spec.decay_mult)))
            offset += span
    total = offset
    n_tiles = total // tile
    tile_lr = np.zeros((n_tiles,), np.float32)
    tile_decay = np.zeros((n_tiles,), np.float32)
    t = 0
    for n, lr_mult, decay in lr_spans:
        tile_lr[t:t + n] = lr_mult
        tile_decay[t:t + n] = decay
        t += n
    return ArenaLayout(
        entries=tuple(entries), struct=tuple(struct), tile=tile,
        total=total, n_tiles=n_tiles, rule=cfg.solver_type,
        n_slots=FUSED_RULE_SLOTS[cfg.solver_type],
        storage_dtype=storage_dtype, tile_lr=tile_lr,
        tile_decay=tile_decay)


def pack(layout: ArenaLayout, tree) -> jax.Array:
    """Blob tree ({lname: [blob, ...]}) -> one [total] arena in the
    storage dtype, pad zones zero.  Differentiable (pad+concat)."""
    parts = []
    for e in layout.entries:
        if e.span == 0:
            continue
        flat = jnp.ravel(tree[e.lname][e.index]).astype(layout.storage)
        if e.span > e.size:
            flat = jnp.pad(flat, (0, e.span - e.size))
        parts.append(flat)
    if not parts:
        return jnp.zeros((0,), layout.storage)
    return jnp.concatenate(parts)


def unpack(layout: ArenaLayout, arena: jax.Array) -> dict:
    """[total] arena -> blob tree, each blob cast back to its recorded
    dtype.  Differentiable: slice+reshape+cast, whose VJP is exactly
    the pad+concat ``pack`` performs — so grads taken w.r.t. the arena
    arrive already packed, with zero cotangent in the pad zones."""
    out: dict = {lname: [None] * n for lname, n in layout.struct}
    for e in layout.entries:
        if e.span == 0:
            blob = jnp.zeros(e.shape, jnp.dtype(e.dtype))
        else:
            seg = jax.lax.slice(arena, (e.offset,), (e.offset + e.size,))
            blob = seg.reshape(e.shape).astype(jnp.dtype(e.dtype))
        out[e.lname][e.index] = blob
    return out


def pack_slots(layout: ArenaLayout, slots) -> list:
    """Blob-wise history ({lname: [[h0, h1?] per blob]}) -> one arena
    per slot index."""
    return [
        pack(layout, {ln: [hl[k] for hl in per_param]
                      for ln, per_param in slots.items()})
        for k in range(layout.n_slots)
    ]


def unpack_slots(layout: ArenaLayout, arenas: list) -> dict:
    """Inverse of :func:`pack_slots` (blob dtypes restored)."""
    per_k = [unpack(layout, a) for a in arenas]
    return {
        lname: [[per_k[k][lname][i] for k in range(layout.n_slots)]
                for i in range(n)]
        for lname, n in layout.struct
    }


def init_slot_arenas(layout: ArenaLayout) -> list:
    """Zero history arenas (the PresolveHistory analog, flat)."""
    return [jnp.zeros((layout.total,), layout.storage)
            for _ in range(layout.n_slots)]


def update_statics(cfg) -> UpdateStatics:
    """SolverConfig -> the kernel's trace-time constants."""
    return UpdateStatics(
        momentum=float(cfg.momentum),
        momentum2=float(cfg.momentum2),
        rms_decay=float(cfg.rms_decay),
        delta=float(cfg.delta),
        iter_size=int(cfg.iter_size),
        # AdamW's decay is decoupled: the rule takes it, not the gradient
        reg=("none" if cfg.weight_decay == 0.0 or cfg.solver_type == "AdamW"
             else "l1" if cfg.regularization_type == "L1" else "l2"),
        clip=cfg.clip_gradients > 0,
    )


def arena_apply_update(cfg, layout: ArenaLayout, param_arena, grad_arena,
                       slot_arenas, rate, it, force: str | None = None):
    """One full Caffe-ordered update over the arenas — the fused twin
    of ``updates.apply_update``.  The traced scalars the kernel cannot
    close over (lr for this iter, the global-norm clip scale computed
    host-of-kernel from the grad arena, adam's bias correction, adamw's
    second) ride a [4] f32 operand; everything else is trace-time
    static.  Returns
    (new_param_arena, new_slot_arenas)."""
    if cfg.clip_gradients > 0:
        # ref: ClipGradients (sgd_solver.cpp:81-100) on raw accumulated
        # grads; pad zones carry zero cotangent so the arena norm equals
        # the blob-wise global_grad_norm (up to summation order)
        norm = jnp.sqrt(jnp.sum(jnp.square(grad_arena.astype(jnp.float32))))
        clip_scale = jnp.where(norm > cfg.clip_gradients,
                               cfg.clip_gradients / norm, 1.0)
    else:
        clip_scale = jnp.float32(1.0)
    corr2 = jnp.float32(1.0)
    if cfg.solver_type == "Adam":
        # ref: adam_solver.cpp correction with t = iter + 1 (the same
        # formula updates._adam traces; computed once per step here
        # instead of per element)
        t = jnp.asarray(it, jnp.float32) + 1.0
        corr = (jnp.sqrt(1.0 - jnp.power(cfg.momentum2, t))
                / (1.0 - jnp.power(cfg.momentum, t)))
    elif cfg.solver_type == "AdamW":
        # updates._adamw's two bias corrections, as factors
        t = jnp.asarray(it, jnp.float32) + 1.0
        corr = 1.0 / (1.0 - jnp.power(cfg.momentum, t))
        corr2 = 1.0 / (1.0 - jnp.power(cfg.momentum2, t))
    else:
        corr = jnp.float32(1.0)
    scalars = jnp.stack([jnp.asarray(rate, jnp.float32),
                         jnp.asarray(clip_scale, jnp.float32),
                         jnp.asarray(corr, jnp.float32),
                         jnp.asarray(corr2, jnp.float32)])
    return fused_update(
        cfg.solver_type, update_statics(cfg), param_arena, grad_arena,
        slot_arenas, jnp.asarray(layout.tile_lr),
        jnp.asarray(layout.tile_decay), scalars, force=force)
