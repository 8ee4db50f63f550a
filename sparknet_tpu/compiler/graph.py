"""NetParameter -> jit-compilable network.

TPU-native replacement for Caffe's Net DAG compiler/executor
(ref: caffe/src/caffe/net.cpp: Init topological wiring :40-540,
ForwardFromTo :565-583, BackwardFromTo :635-646).  Differences by design:

- The "executor" is a pure function ``apply(variables, feeds)`` traced once
  under ``jax.jit``; XLA does scheduling/fusion, so there is no layer loop
  at runtime and no Forward/Backward ranges.
- Backward is ``jax.grad`` of the scalar loss; Caffe's InsertSplits diff
  accumulation (net.cpp:54) is what autodiff does natively, so no split
  layers are materialized.
- Blobs are dict entries during tracing; in-place prototxt tops (top ==
  bottom) are plain rebinds, and XLA's buffer aliasing recovers the memory
  sharing Caffe engineered by hand.

Phase filtering follows NetStateRule semantics (net.cpp:287 FilterNet +
StateMeetsRule: phase / min_level / max_level / stage / not_stage).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from sparknet_tpu.common import (
    Phase,
    act_storage_policy,
    get_config,
    layer_key,
)
from sparknet_tpu.ops import create_layer
from sparknet_tpu.ops.base import Layer, ParamSpec
from sparknet_tpu.ops.data_layers import InputLayer
from sparknet_tpu.proto.text_format import Message

Params = dict[str, list[jax.Array]]
State = dict[str, dict[str, jax.Array]]

# The per-block remat boundary tag (Config.remat == "blocks"): pooling
# outputs are a CNN's natural block edges (each conv/relu stack drains
# into one), so ``apply`` names them via ``jax.ad_checkpoint.
# checkpoint_name`` and the "blocks" checkpoint policy
# (solvers/solver.py apply_remat: save_only_these_names) keeps exactly
# these alive for backward — everything inside a block recomputes.
# Families with no pooling layers (transformer) degrade to the "full"
# policy's save-nothing behavior, which keeps the bytecheck
# monotonicity contract (more recompute => never more saved bytes).
BLOCK_SAVE_NAME = "sparknet_block_boundary"


@dataclasses.dataclass
class NetVars:
    """All network variables: learnable params + mutable state (BN stats).

    Registered as a pytree so it can cross jit boundaries directly."""

    params: Params
    state: State

    def tree_flatten(self):
        return (self.params, self.state), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    NetVars, NetVars.tree_flatten, NetVars.tree_unflatten
)


def _rule_matches(rule: Message, phase: Phase, level: int, stages: set[str]) -> bool:
    """ref: Net::StateMeetsRule (net.cpp:287+)."""
    if rule.has("phase") and rule.get_str("phase") != phase.name:
        return False
    if rule.has("min_level") and level < rule.get_int("min_level"):
        return False
    if rule.has("max_level") and level > rule.get_int("max_level"):
        return False
    for s in rule.get_all("stage"):
        if str(s) not in stages:
            return False
    for s in rule.get_all("not_stage"):
        if str(s) in stages:
            return False
    return True


def filter_phase(
    net_param: Message,
    phase: Phase,
    level: int = 0,
    stages: set[str] | None = None,
) -> list[Message]:
    """Select the layers active in ``phase`` (ref: Net::FilterNet)."""
    stages = stages or set()
    out = []
    for lp in net_param.get_all("layer") or net_param.get_all("layers"):
        includes = lp.get_all("include")
        excludes = lp.get_all("exclude")
        keep = True
        if includes:
            keep = any(_rule_matches(r, phase, level, stages) for r in includes)
        elif excludes:
            keep = not any(_rule_matches(r, phase, level, stages) for r in excludes)
        if keep:
            out.append(lp)
    return out


@dataclasses.dataclass
class BlobInfo:
    shape: tuple[int, ...]
    dtype: Any


# The device scope around a looped region, all passes: ``LOOP.<name>``
# (the region's layers keep their own ``L.<name>`` scopes inside it, one
# scope a layer with every pass under it); in common.CACHE_SCOPES
LOOP_SCOPE = "LOOP."


@dataclasses.dataclass(frozen=True)
class LoopRegion:
    """A net-level ``loop { name count first last carry_in carry_out
    collect { blob top } }``: the consecutive layers ``first`` .. ``last``
    (indices into ``Network.layers``) run ``count`` times on ONE set of
    parameters.  Pass 1 reads the blob ``carry_in`` as the net made it;
    pass t + 1 reads, under that name, what pass t left in ``carry_out``.
    After the region ``carry_out`` holds the last pass's value and each
    ``collect`` top every pass's value of its blob, pass-major along
    axis 0 ([count * N, ...]: pass t's rows are [t N, (t + 1) N)), so the
    layers behind the region see an ordinary [N, ...] blob.  The
    region's other tops do not leave it."""

    name: str
    count: int
    first: int
    last: int
    carry_in: str
    carry_out: str
    collect: tuple[tuple[str, str], ...]


class Network:
    """A phase-specific compiled view of a NetParameter.

    ``init(key, feed_shapes)`` -> NetVars;
    ``apply(vars, feeds, rng)`` -> (blobs, new_state, total_loss).
    Both are pure and jit-safe; ``apply`` is what pjit shards over the mesh.
    """

    def __init__(
        self,
        net_param: Message,
        phase: Phase = Phase.TRAIN,
        batch_override: int | None = None,
        stages: set[str] | None = None,
        level: int = 0,
    ):
        from sparknet_tpu.proto.upgrade import upgrade_net

        net_param = upgrade_net(net_param)
        self.net_param = net_param
        self.phase = phase
        self.name = net_param.get_str("name", "net")
        self.batch_override = batch_override
        self.stages = set(stages or ())
        self.layers: list[Layer] = [
            create_layer(lp, phase)
            for lp in filter_phase(net_param, phase, level, self.stages)
        ]
        # Caffe never enforces unique layer names; the zoo relies on that
        # (mnist_autoencoder has two param-less "loss" layers in TRAIN).
        # Duplicates are fine until two same-name layers both own params —
        # the params pytree is keyed by name, so THAT collides (checked in
        # init(), where param ownership is known).
        self.input_layers = [l for l in self.layers if isinstance(l, InputLayer)]
        # External feed blobs: tops of input layers that aren't self-feeding.
        self.feed_blobs: list[str] = []
        for l in self.input_layers:
            if not getattr(l, "SELF_FEEDING", False):
                self.feed_blobs.extend(l.tops)
        # net-level legacy inputs: `input: "data"` + input_shape/input_dim
        self.net_inputs = self._net_level_inputs()
        self.feed_blobs.extend(n for n, _ in self.net_inputs)
        self._blob_info: dict[str, BlobInfo] | None = None
        # Cross-layer weight sharing via `param { name: ... }` (ref:
        # net.cpp:470+ AppendParam shared-blob wiring; the siamese example's
        # two towers).  First occurrence of a name owns the array; later
        # (layer, idx) positions alias it — apply() substitutes the owner's
        # array, so autodiff accumulates every tower's gradient into it,
        # exactly Caffe's shared-diff accumulation.  Ownership is elected
        # over the UNFILTERED layer list so train/test phase views agree on
        # the owner (phases share one variables pytree via the Solver).
        self.param_aliases: dict[tuple[str, int], tuple[str, int]] = {}
        self._shared_names: dict[tuple[str, int], str] = {}
        owners: dict[str, tuple[str, int]] = {}
        phase_names = {l.name for l in self.layers}
        for lp in net_param.get_all("layer") or net_param.get_all("layers"):
            lname = lp.get_str("name")
            for i, pm in enumerate(lp.get_all("param")):
                pname = pm.get_str("name", "")
                if not pname:
                    continue
                if pname in owners:
                    if lname in phase_names and owners[pname][0] != lname:
                        self.param_aliases[(lname, i)] = owners[pname]
                        self._shared_names[(lname, i)] = pname
                else:
                    owners[pname] = (lname, i)
        self.loops: list[LoopRegion] = self._loop_regions()
        self._loop_of = {i: r for r in self.loops
                         for i in range(r.first, r.last + 1)}

    # -- looped regions (``loop { ... }``, no Caffe analog) -----------------
    def _loop_regions(self) -> list[LoopRegion]:
        """The net's ``loop`` messages against this phase's layers.  A
        region none of whose layers are in the phase is not in it either."""
        names = [l.name for l in self.layers]
        out: list[LoopRegion] = []
        for lm in self.net_param.get_all("loop"):
            name = lm.get_str("name", "loop")
            ends = [lm.get_str("first"), lm.get_str("last")]
            if not any(e in names for e in ends):
                continue
            if not all(names.count(e) == 1 for e in ends):
                raise ValueError(
                    f"loop {name!r}: first / last {ends} must each name one "
                    f"layer of the {self.phase.name} net")
            first, last = (names.index(e) for e in ends)
            count = lm.get_int("count", 1)
            if first > last or count < 1:
                raise ValueError(
                    f"loop {name!r}: wants first <= last and count >= 1, "
                    f"got layers #{first} .. #{last}, count {count}")
            if out and first <= out[-1].last:
                raise ValueError(
                    f"loop {name!r} overlaps loop {out[-1].name!r} (regions "
                    "are listed in the layers' order and do not nest)")
            inside = self.layers[first:last + 1]
            bad = [l.name for l in inside if isinstance(l, InputLayer)
                   or l.IS_LOSS or any(w != 0.0 for w in l.loss_weights())]
            if bad:
                raise ValueError(
                    f"loop {name!r}: input and loss layers stay outside a "
                    f"looped region: {bad}")
            region = LoopRegion(
                name, count, first, last, lm.get_str("carry_in"),
                lm.get_str("carry_out"),
                tuple((c.get_str("blob"), c.get_str("top"))
                      for c in lm.get_all("collect")))
            tops = {t for l in inside for t in l.tops}
            lost = [b for b in (region.carry_out,
                                *(b for b, _ in region.collect))
                    if b not in tops]
            if lost or not region.carry_in:
                raise ValueError(
                    f"loop {name!r}: carry_in must be named, and carry_out "
                    f"and every collected blob a top of the region: {lost}")
            out.append(region)
        return out

    # -- legacy net-level inputs (ref: net.cpp AppendTop "deprecated 4D input
    # dimensions" / input_shape) ------------------------------------------
    def _net_level_inputs(self) -> list[tuple[str, tuple[int, ...] | None]]:
        # declared dims are canonical Caffe blob order; the feed contract
        # is the INTERNAL orientation (Config.layout, ops/layout.py)
        from sparknet_tpu.ops.layout import internal_shape

        names = [str(s) for s in self.net_param.get_all("input")]
        shapes: list[tuple[int, ...] | None] = []
        shape_msgs = self.net_param.get_all("input_shape")
        dims_flat = [int(d) for d in self.net_param.get_all("input_dim")]
        for i, _ in enumerate(names):
            if i < len(shape_msgs):
                shapes.append(internal_shape(
                    tuple(int(d) for d in shape_msgs[i].get_all("dim"))))
            elif dims_flat:
                shapes.append(internal_shape(
                    tuple(dims_flat[4 * i : 4 * i + 4])))
            else:
                shapes.append(None)
        return list(zip(names, shapes))

    # ------------------------------------------------------------------
    def feed_shapes(self) -> dict[str, tuple[int, ...]]:
        """Declared shapes for feed blobs (from layer params), where known."""
        out: dict[str, tuple[int, ...]] = {}
        for l in self.input_layers:
            if getattr(l, "SELF_FEEDING", False):
                continue
            shapes = l.blob_shapes(self.batch_override)
            if shapes:
                for top, shape in zip(l.tops, shapes):
                    out[top] = shape
        for name, shape in self.net_inputs:
            if shape:
                out[name] = shape
        return out

    # ------------------------------------------------------------------
    def init(
        self,
        key: jax.Array,
        feed_shapes: dict[str, tuple[int, ...]] | None = None,
        feed_dtypes: dict[str, Any] | None = None,
    ) -> NetVars:
        """Initialize params/state, propagating shapes layer by layer with
        abstract evaluation (no FLOPs, no device memory)."""
        shapes = dict(self.feed_shapes())
        if feed_shapes:
            shapes.update(feed_shapes)
        dtypes = dict(feed_dtypes or {})
        blob: dict[str, jax.ShapeDtypeStruct] = {}
        for name in self.feed_blobs:
            if name not in shapes:
                raise ValueError(
                    f"no shape known for input blob {name!r}; pass feed_shapes"
                )
            blob[name] = jax.ShapeDtypeStruct(shapes[name], dtypes.get(name, jnp.float32))
        params: Params = {}
        state: State = {}
        region_of = self._loop_of
        for idx, layer in enumerate(self.layers):
            sub = layer_key(key, idx)
            if isinstance(layer, InputLayer):
                if getattr(layer, "SELF_FEEDING", False):
                    for top, val in zip(layer.tops, layer.constant_values()):
                        blob[top] = jax.ShapeDtypeStruct(val.shape, val.dtype)
                continue
            in_shapes = [blob[b].shape for b in layer.bottoms]
            p, s = layer.init(sub, in_shapes)
            # an alias position the layer never materializes would otherwise
            # be silently skipped and train unshared (Caffe CHECK-fails,
            # ref: net.cpp:470+ AppendParam)
            for (aname, ai), pname in self._shared_names.items():
                if aname == layer.name and ai >= len(p or []):
                    raise ValueError(
                        f"param name {pname!r} at position {ai} of layer "
                        f"{aname!r}, which has only {len(p or [])} learnable "
                        "blob(s) — sharing would be silently dropped"
                    )
            if p and self.param_aliases:
                # aliased positions store a 0-size placeholder; the real
                # array lives at (and is updated through) the owner only
                checked = []
                for i, arr in enumerate(p):
                    owner = self.param_aliases.get((layer.name, i))
                    if owner is None:
                        checked.append(arr)
                        continue
                    pname = self._shared_names.get((layer.name, i), "?")
                    olist = params.get(owner[0])
                    if olist is None or owner[1] >= len(olist):
                        raise ValueError(
                            f"Cannot share param {pname!r}: owner layer "
                            f"{owner[0]!r} (position {owner[1]}) declares "
                            "no such blob (is the param{} on a param-less "
                            "or later layer?)"
                        )
                    if tuple(olist[owner[1]].shape) != tuple(arr.shape):
                        raise ValueError(
                            f"Cannot share param {pname!r}: owner "
                            f"{owner[0]}[{owner[1]}] has shape "
                            f"{tuple(olist[owner[1]].shape)} but "
                            f"{layer.name}[{i}] expects {tuple(arr.shape)}"
                        )
                    checked.append(jnp.zeros((0,), arr.dtype))
                p = checked
            if p:
                # every name-keyed lookup (params, param_specs_for,
                # layer_by_name, snapshot layout) would bind ambiguously —
                # a param OWNER may not share its name with ANY other layer
                if sum(1 for l2 in self.layers if l2.name == layer.name) > 1:
                    raise ValueError(
                        f"param-owning layer {layer.name!r} shares its name "
                        "with another layer; rename one (params are keyed "
                        "by layer name, matching Caffe snapshot layout)"
                    )
                params[layer.name] = p
            if s:
                if layer.name in state:
                    raise ValueError(
                        f"two stateful layers share the name {layer.name!r}"
                    )
                if idx in region_of:
                    raise ValueError(
                        f"layer {layer.name!r} keeps state and lies in the "
                        f"looped region {region_of[idx].name!r}: a region's "
                        "passes share parameters, not state")
                state[layer.name] = s
            outs = self._abstract_apply(
                layer,
                self._resolve_shared(layer, p, params),
                s,
                [blob[b] for b in layer.bottoms],
            )
            for top, o in zip(layer.tops, outs):
                blob[top] = jax.ShapeDtypeStruct(o.shape, o.dtype)
            region = region_of.get(idx)
            if region is not None and idx == region.last:
                # the shapes of one pass are the shapes of every pass
                cin, cout = blob[region.carry_in], blob[region.carry_out]
                if cin.shape != cout.shape:
                    raise ValueError(
                        f"loop {region.name!r}: carry_out {region.carry_out!r}"
                        f" {cout.shape} must have the shape of carry_in "
                        f"{region.carry_in!r} {cin.shape}")
                for b, top in region.collect:
                    blob[top] = jax.ShapeDtypeStruct(
                        (region.count * blob[b].shape[0], *blob[b].shape[1:]),
                        blob[b].dtype)
        self._blob_info = {k: BlobInfo(v.shape, v.dtype) for k, v in blob.items()}
        return NetVars(params=params, state=state)

    def _resolve_shared(self, layer, p, all_params):
        """Substitute owner arrays for aliased param positions."""
        if not self.param_aliases or not p:
            return p
        out = list(p)
        for i in range(len(out)):
            owner = self.param_aliases.get((layer.name, i))
            if owner is not None:
                olist = all_params.get(owner[0])
                if olist is None or owner[1] >= len(olist):
                    pname = self._shared_names.get((layer.name, i), "?")
                    raise ValueError(
                        f"Cannot share param {pname!r}: owner {owner[0]!r} "
                        f"has no params in this variables pytree (the owner "
                        "layer may be filtered out of the phase that "
                        "initialized the net)"
                    )
                out[i] = olist[owner[1]]
        return out

    def _abstract_apply(self, layer, p, s, in_structs):
        train = self.phase == Phase.TRAIN

        def f(p_, s_, xs):
            return layer.apply(p_, s_, xs, train=train, rng=jax.random.key(0)).outputs

        return jax.eval_shape(f, p, s, list(in_structs))

    def blob_info(self) -> dict[str, BlobInfo]:
        if self._blob_info is None:
            raise RuntimeError("call init() first")
        return self._blob_info

    # ------------------------------------------------------------------
    def layer_index(self, name: str) -> int:
        for i, layer in enumerate(self.layers):
            if layer.name == name:
                return i
        raise KeyError(
            f"no layer named {name!r}; layers: {[l.name for l in self.layers]}"
        )

    def apply(
        self,
        variables: NetVars,
        feeds: dict[str, jax.Array],
        rng: jax.Array | None = None,
        *,
        train: bool | None = None,
        start: str | None = None,
        end: str | None = None,
        debug_sink: dict | None = None,
    ) -> tuple[dict[str, jax.Array], State, jax.Array]:
        """Forward pass. Returns (all blobs, updated state, total weighted loss).

        ``debug_sink``: when a dict is passed, every executed layer
        records ``(layer_name, top_name) -> mean(|output|)`` into it AT
        EXECUTION TIME — in-place ops get their own entry with their own
        post-op value, unlike the final blob dict where a rebind
        overwrites its producer (ref: Net::ForwardDebugInfo,
        net.cpp:658-683).

        ``start``/``end`` name the first/last layer to run — the partial
        execution of Net::ForwardFromTo (net.cpp:565-583; pycaffe's
        ``net.forward(start=..., end=...)``).  A partial run takes its
        inputs from ``feeds`` (feed the start layer's bottom blobs).
        Loss accumulates over the executed range only.

        ref: Net::ForwardFromTo (net.cpp:565-583) + loss accumulation
        (layer.hpp Forward loss() * loss_weight)."""
        train = (self.phase == Phase.TRAIN) if train is None else train
        si = 0 if start is None else self.layer_index(start)
        ei = len(self.layers) - 1 if end is None else self.layer_index(end)
        if si > ei:
            raise ValueError(
                f"start layer {start!r} (#{si}) comes after end layer "
                f"{end!r} (#{ei})"
            )
        # Mixed precision (Config.compute_dtype, default f32): master params
        # and optimizer state stay in param_dtype; activations and the conv/
        # matmul FLOPs run in compute_dtype (bf16 keeps the MXU at full
        # rate).  Loss layers always compute in f32; state updates
        # (BatchNorm stats) are cast back to their stored dtype.
        cdt = get_config().compute_dtype
        mixed = cdt != jnp.float32
        # block-boundary tagging is trace-time and strictly gated: with
        # Config.remat != "blocks" (the default) no name primitive is
        # emitted and the traced program is byte-identical to the
        # banked manifests
        tag_blocks = get_config().remat == "blocks"
        # bf16 activation STORAGE (Config.activation_dtype, default off):
        # the named boundaries store bf16, but every layer upcasts its
        # inputs to compute_dtype before compute — accumulation stays
        # f32, loss/BN statistics stay pinned f32 (the numcheck
        # contracts).  Off path takes none of the branches below: the
        # traced program is byte-identical to the banked manifests.
        act_policy = act_storage_policy()
        act_store_io = act_policy in ("io", "full")

        def _cast(x, dt):
            return (
                x.astype(dt)
                if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                else x
            )

        blob: dict[str, jax.Array] = {}
        if si > 0:
            # mid-graph starts are primed with whatever the caller
            # supplies (the start layer's bottoms — possibly intermediate
            # blobs); end-only runs still begin at layer 0 and keep the
            # strict input-feed contract below
            for name, val in feeds.items():
                blob[name] = _cast(val, cdt) if mixed else val
                if act_store_io:
                    blob[name] = _cast(blob[name], jnp.bfloat16)
        else:
            for name in self.feed_blobs:
                if name not in feeds:
                    raise ValueError(f"missing feed for input blob {name!r}")
                blob[name] = _cast(feeds[name], cdt) if mixed else feeds[name]
                if act_store_io:
                    blob[name] = _cast(blob[name], jnp.bfloat16)
        new_state: State = {}
        total_loss = jnp.zeros((), jnp.float32)

        def run(layer, blob, sub, sink):
            """One layer on ``blob``: its tops land there, its state in
            ``new_state``; returns its (weight, top) loss terms."""
            p = self._resolve_shared(
                layer, variables.params.get(layer.name, []), variables.params
            )
            s = variables.state.get(layer.name, {})
            missing = [b for b in layer.bottoms if b not in blob]
            if missing:
                raise ValueError(
                    f"layer {layer.name!r} needs blob(s) {missing}; feed "
                    "them or start the run at an earlier layer"
                )
            ins = [blob[b] for b in layer.bottoms]
            if mixed or act_policy:
                if layer.IS_LOSS:
                    ins = [_cast(x, jnp.float32) for x in ins]
                else:
                    if mixed:
                        p = [x if i in layer.F32_BLOBS else _cast(x, cdt)
                             for i, x in enumerate(p)]
                    if act_policy:
                        # upcast stored-bf16 inputs back to the compute
                        # dtype: storage is the only thing that narrows
                        ins = [_cast(x, cdt) for x in ins]
            # the scope lands in HLO op metadata, letting profiler traces
            # attribute fused-op time back to prototxt layers (tpunet
            # time --trace); '/' would nest scopes, so flatten it
            with jax.named_scope("L." + layer.name.replace("/", ".")):
                out = layer.apply(p, s, ins, train=train, rng=sub)
            if act_policy and not layer.IS_LOSS and (
                    act_policy == "full"
                    or (act_policy == "blocks" and layer.type == "Pooling")):
                # storage cast BEFORE the checkpoint_name tag so a
                # composed remat="blocks" run saves the bf16 tensor
                out = dataclasses.replace(out, outputs=[
                    _cast(o, jnp.bfloat16) for o in out.outputs])
            if tag_blocks and layer.type == "Pooling":
                from jax.ad_checkpoint import checkpoint_name

                out = dataclasses.replace(out, outputs=[
                    checkpoint_name(o, BLOCK_SAVE_NAME)
                    for o in out.outputs])
            if out.state:
                if mixed and layer.name in variables.state:
                    prev = variables.state[layer.name]
                    out_state = {
                        k: _cast(v, prev[k].dtype) if k in prev else v
                        for k, v in out.state.items()
                    }
                else:
                    out_state = out.state
                new_state[layer.name] = out_state
            for top, o in zip(layer.tops, out.outputs):
                blob[top] = o
                if sink is not None and o.size:
                    sink[(layer.name, top)] = jnp.mean(jnp.abs(o))
            return [(w, o) for w, o in zip(layer.loss_weights(), out.outputs)
                    if w != 0.0]

        for idx, layer in enumerate(self.layers):
            if idx < si or idx > ei:
                continue
            region = self._loop_of.get(idx)
            if region is not None:  # the whole region runs at its first layer
                if (idx == si and idx != region.first) or ei < region.last:
                    raise ValueError(
                        f"a partial run starts or ends at {layer.name!r}, "
                        f"inside the looped region {region.name!r}")
                if idx == region.first:
                    self._apply_loop(region, blob, run, rng, debug_sink)
                continue
            sub = layer_key(rng, idx) if rng is not None else None
            if isinstance(layer, InputLayer):
                if getattr(layer, "SELF_FEEDING", False):
                    for top, val in zip(layer.tops, layer.constant_values()):
                        blob[top] = val
                continue
            for w, o in run(layer, blob, sub, debug_sink):
                total_loss = total_loss + w * jnp.sum(o).astype(jnp.float32)
        # carry forward unmodified state so the pytree structure is stable
        for lname, s in variables.state.items():
            new_state.setdefault(lname, s)
        return blob, new_state, total_loss

    def _apply_loop(self, region: LoopRegion, blob, run, rng, sink) -> None:
        """The region's layers, ``count`` times, EXPANDED here: declared
        once (one set of parameters in the prototxt, ``NetVars.params``, a
        snapshot and the optimizer), traced once a pass, so a looped
        blob's gradient is autodiff's sum over the passes.  ``run`` is
        ``apply``'s one-layer step; every pass sees the blobs made before
        the region.

        One ``lax.scan`` over the passes (each block ONCE in the program)
        was built first and measured (PERF.md section 6, PR 41): scan
        keeps every residual of its body stacked [count, ...], also those
        XLA recomputes for nothing in the straight-line program (the f32
        copy of each norm's input, SiLU's factors): at the benchmark's
        looped decoder 13.3 GB of temporaries against 7.1, which does
        not fit the chip, and at half the length a step 22 % slower."""
        layers = list(enumerate(self.layers))[region.first:region.last + 1]
        if region.carry_in not in blob:
            raise ValueError(
                f"loop {region.name!r} needs blob {region.carry_in!r}")
        carry = blob[region.carry_in]
        kept: list[list] = [[] for _ in region.collect]
        with jax.named_scope(LOOP_SCOPE + region.name.replace("/", ".")):
            for t in range(region.count):
                local = dict(blob)
                local[region.carry_in] = carry
                for idx, layer in layers:
                    sub = None if rng is None else jax.random.fold_in(
                        layer_key(rng, idx), t)
                    run(layer, local, sub, sink)
                carry = local[region.carry_out]
                for rows, (b, _) in zip(kept, region.collect):
                    rows.append(local[b])
            for rows, (_, top) in zip(kept, region.collect):
                blob[top] = jnp.concatenate(rows, axis=0)
        blob[region.carry_out] = carry

    # ------------------------------------------------------------------
    def param_specs_for(self, variables: NetVars) -> dict[str, list[ParamSpec]]:
        """lr_mult/decay_mult per blob per layer, for the solver
        (ref: net.cpp:470+ AppendParam; params_lr_/params_weight_decay_)."""
        return {
            lname: next(l for l in self.layers if l.name == lname).param_specs(len(plist))
            for lname, plist in variables.params.items()
        }

    def output_blobs(self) -> list[str]:
        """Tops never consumed as a bottom — the net's outputs
        (ref: net.cpp AppendTop/available_blobs bookkeeping; for a test net
        these are what TestAndStoreResult accumulates, solver.cpp:414-444)."""
        consumed = set()
        for l in self.layers:
            for b in l.bottoms:
                if b not in l.tops:  # in-place use doesn't consume
                    consumed.add(b)
        outs: list[str] = []
        for l in self.layers:
            for t in l.tops:
                if t not in consumed and t not in outs:
                    outs.append(t)
        return outs

    def layer_by_name(self, name: str) -> Layer:
        for l in self.layers:
            if l.name == name:
                return l
        raise KeyError(name)

    def __repr__(self):
        return f"<Network {self.name!r} phase={self.phase.name} layers={len(self.layers)}>"
