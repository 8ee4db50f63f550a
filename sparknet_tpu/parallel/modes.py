"""Parallel-mode registry: mode name -> traceable train-step factory.

The graph-contract analysis (``sparknet_tpu/analysis/graphcheck.py``)
needs, for every parallel mode the framework ships, a jitted step
function plus concrete example arguments it can ``.lower()`` on the
virtual 8-device CPU mesh WITHOUT executing a single step.  This module
is that seam: each factory builds the same trainer objects
``dryrun_multichip`` exercises (ref: __graft_entry__.py modes 1-13) but
stops at the jitted callable, exposing everything the static audits
need — carry structure for the donation audit, intended param
shardings for the sharding audit, byte totals for the comm model.

Kept in ``parallel/`` (not ``analysis/``) because it imports jax and
the trainer stack; the analysis package stays stdlib-importable and
pulls this in lazily only when the ``graph`` subcommand actually runs.

Modes mirror the communication design space of the paper and its
TPU-first extensions: ``solo`` (no mesh — the negative control: any
collective is a bug), ``dp``/``dp_bf16``/``mobilenet_dp`` (tau=1
GSPMD sync SGD, ref: CifarApp.scala:95-136 degenerate case), ``tau``
(the SparkNet tau-averaging round), ``easgd`` (elastic coupling),
``solo_nhwc``/``dp_nhwc`` (the channels-last layout twins — identical
comm contracts, plus the layout transpose census), ``tp``
(Megatron-style output-channel sharding), ``sp`` (Ulysses all-to-all
sequence parallelism — the ring impl is trace-broken under
the pinned jax, see test_seq_parallel's seed state), ``gpipe``
(pipeline ppermute), ``moe`` (expert all_to_all dispatch),
``elastic_w{8,6,4}`` (width-parameterized τ-averaging twins),
``serve_b{1,8,64,256}`` (the serving engine's AOT bucket forwards —
single-chip, forward-only, zero collectives), and
``solo_remat``/``dp_remat`` (the rematerialization twins — the banked
bytes-minimal ``Config.remat`` policy from
``docs/byte_contracts/remat_policy.json`` routed through the same
build, identical comm contracts; they exist to prove the byte model's
modeled saved-activation drop lowers as predicted), and
``solo_act_bf16``/``dp_act_bf16`` (the activation-storage twins — the
banked bytes-minimal safe ``Config.activation_dtype`` policy from
``docs/num_contracts/mixed_policy.json`` routed the same way; they
prove the numcheck mixed-precision search's bf16-storage-with-f32-
accumulation schedule lowers as predicted).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TraceTarget", "MODES", "build_target", "list_modes"]


@dataclasses.dataclass
class TraceTarget:
    """Everything graphcheck needs to lower + audit one mode.

    ``fn(*args)`` is a jitted callable; ``alt_args`` is a second
    argument tuple with identical shapes/dtypes (typically the
    iteration counter bumped) — lowering both must produce identical
    StableHLO or the step recompiles every iteration.
    ``carry_argnums`` are the positions whose buffers thread between
    rounds (must be donated); the first ``carry_out_leaves`` flattened
    outputs are that carry coming back (their shardings must match the
    inputs' or every round pays a reshard).
    """

    name: str
    fn: Any
    args: tuple
    meta: dict
    param_bytes: int
    state_bytes: int
    carry_argnums: tuple = ()
    carry_out_leaves: int = 0
    alt_args: tuple | None = None
    # context entered around lower()/compile(): trace-time config such
    # as compute_dtype and the sequence-parallel attention routing
    trace_context: Callable[[], Any] = contextlib.nullcontext
    # tp/moe-style modes declare that at least one param MUST be sharded
    expects_sharded_params: bool = False


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "shape"))


def _feeds_for(family, batch: int, rs: np.random.RandomState,
               tau: int = 0) -> dict:
    """Synthetic feeds matching the family's RDD layer shapes (in the
    active internal layout — ops/layout.py); a leading [tau] axis when
    the round carries tau local steps."""
    if family.feed == "tokens":
        data = rs.randint(0, family.vocab, (batch, family.seq_len))
        data = data.astype(np.int32)
    else:
        from sparknet_tpu.ops.layout import internal_shape

        shape = internal_shape((batch, *family.image_shape))
        data = rs.randn(*shape).astype(np.float32) * 10
    label = rs.randint(0, family.num_classes, batch).astype(np.int32)
    if tau:
        data = np.stack([data] * tau)
        label = np.stack([label] * tau)
    return {"data": data, "label": label}


def _trainer_target(name: str, family_name: str, mesh, *, tau: int = 1,
                    elastic_alpha: float = 0.0, per_device_batch: int = 2,
                    rules=None, compute_dtype=None, layout=None,
                    remat: str | None = None, act: str | None = None,
                    expects_sharded_params: bool = False) -> TraceTarget:
    """The shared trainer-mode factory: construct Solver+ParallelTrainer
    exactly as the dryrun does, stop at the jitted round function.
    ``layout``: internal activation layout for the whole build+trace
    (None = leave the global config alone).  ``remat``:
    rematerialization policy (Config.remat) for the whole build+trace —
    the dp_remat twin routes the banked byte-minimal policy here.
    ``act``: activation-storage policy (Config.activation_dtype) — the
    dp_act_bf16 twin routes the banked numcheck mixed-policy winner
    here."""
    from sparknet_tpu.common import get_config, set_config
    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    from sparknet_tpu.solvers.solver import Solver

    family = GRAPH_SWEEP_FAMILIES[family_name]
    cfg = get_config()
    data_size = mesh.shape.get(cfg.data_axis, 1)
    B_global = per_device_batch * data_size

    @contextlib.contextmanager
    def dtype_ctx():
        overrides = {}
        if compute_dtype is not None:
            overrides["compute_dtype"] = compute_dtype
        if layout is not None:
            overrides["layout"] = layout
        if remat is not None:
            overrides["remat"] = remat
        if act is not None:
            overrides["activation_dtype"] = act
        if not overrides:
            yield
            return
        prior = {k: getattr(get_config(), k) for k in overrides}
        set_config(**overrides)
        try:
            yield
        finally:
            set_config(**prior)

    with dtype_ctx():
        # tau/EASGD rounds run per-worker replicas: the solver's own
        # batch is the per-device slice (dryrun modes 2/7 shape)
        solver_batch = per_device_batch if (tau > 1 or elastic_alpha) \
            else B_global
        solver = Solver(family.solver(), family.net(solver_batch))
        trainer = ParallelTrainer(solver, mesh=mesh, tau=tau,
                                  rules=rules, elastic_alpha=elastic_alpha)
        rs = np.random.RandomState(0)
        stacked = tau > 1 or elastic_alpha > 0
        feeds = trainer._put_feeds(
            _feeds_for(family, B_global, rs, tau=trainer.tau if stacked else 0),
            with_tau_axis=stacked,
        )

    if elastic_alpha:
        args = (trainer.variables, trainer.slots, trainer.center, 0, feeds,
                solver._key)
        alt = args[:3] + (1,) + args[4:]
        carry_argnums: tuple = (0, 1, 2)
        carry_out = sum(len(jax.tree_util.tree_leaves(t)) for t in args[:3])
    else:
        args = (trainer.variables, trainer.slots, 0, feeds, solver._key)
        alt = args[:2] + (1,) + args[3:]
        carry_argnums = (0, 1)
        carry_out = sum(len(jax.tree_util.tree_leaves(t)) for t in args[:2])

    @contextlib.contextmanager
    def trace_ctx():
        with dtype_ctx():
            with trainer._sp_context():
                yield

    meta = {
        "family": family_name,
        "mesh": dict(mesh.shape),
        "tau": trainer.tau,
        "elastic_alpha": elastic_alpha,
        "batch": B_global,
        "dtype": "bf16" if compute_dtype == jnp.bfloat16 else "f32",
        "layout": layout or "nchw",
    }
    if remat is not None:
        meta["remat"] = remat
    if act is not None:
        meta["act"] = act
    return TraceTarget(
        name=name,
        fn=trainer._train,
        args=args,
        alt_args=alt,
        meta=meta,
        # model sizes for the comm model come from the SOLVER's (single-
        # replica) tree: tau/EASGD trainers stack a worker axis, but the
        # pmean still moves one model's bytes per chip per round
        param_bytes=_tree_bytes(solver.variables.params),
        state_bytes=_tree_bytes(solver.variables.state),
        carry_argnums=carry_argnums,
        carry_out_leaves=carry_out,
        trace_context=trace_ctx,
        expects_sharded_params=expects_sharded_params,
    )


# ---------------------------------------------------------------------------
# Mode factories.  Each takes the device list and returns a TraceTarget.
# ---------------------------------------------------------------------------


def _mode_solo(devices, layout: str | None = None,
               name: str = "solo", remat: str | None = None,
               act: str | None = None) -> TraceTarget:
    """Single-chip Solver step — the negative control (no mesh, so the
    lowered program must contain ZERO collectives) and the donation
    audit's original catch: ``Solver._train_step`` shipped undonated
    until this audit flagged the 2x params+slots HBM bloat.
    ``layout="nhwc"`` builds the channels-last twin (mode solo_nhwc),
    whose manifest pins the zero-interior-transpose layout contract;
    ``remat`` builds the rematerialization twin (mode solo_remat) under
    the given Config.remat policy; ``act`` builds the activation-storage twin
    (mode solo_act_bf16) under the given Config.activation_dtype
    policy."""
    from sparknet_tpu.common import get_config, set_config
    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
    from sparknet_tpu.solvers.solver import Solver

    family = GRAPH_SWEEP_FAMILIES["cifar10_quick"]
    B = 16

    @contextlib.contextmanager
    def lay_ctx():
        overrides: dict = {}
        if layout is not None:
            overrides["layout"] = layout
        if remat is not None:
            overrides["remat"] = remat
        if act is not None:
            overrides["activation_dtype"] = act
        if not overrides:
            yield
            return
        prior = {k: getattr(get_config(), k) for k in overrides}
        set_config(**overrides)
        try:
            yield
        finally:
            set_config(**prior)

    with lay_ctx():
        solver = Solver(family.solver(), family.net(B))
        rs = np.random.RandomState(0)
        feeds = {k: jnp.asarray(v)
                 for k, v in _feeds_for(family, B, rs).items()}
    args = (solver.variables, solver.slots, 0, feeds, solver._key)
    carry_out = sum(len(jax.tree_util.tree_leaves(t)) for t in args[:2])
    meta = {"family": "cifar10_quick", "mesh": {}, "tau": 1,
            "batch": B, "dtype": "f32", "layout": layout or "nchw"}
    if remat is not None:
        meta["remat"] = remat
    if act is not None:
        meta["act"] = act
    return TraceTarget(
        name=name, fn=solver._train_step, args=args,
        alt_args=args[:2] + (1,) + args[3:],
        meta=meta,
        param_bytes=_tree_bytes(solver.variables.params),
        state_bytes=_tree_bytes(solver.variables.state),
        carry_argnums=(0, 1), carry_out_leaves=carry_out,
        trace_context=lay_ctx,
    )


def _data_mesh(devices):
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("data",))


def _mode_dp(devices) -> TraceTarget:
    return _trainer_target("dp", "cifar10_quick", _data_mesh(devices))


def _mode_solo_nhwc(devices) -> TraceTarget:
    return _mode_solo(devices, layout="nhwc", name="solo_nhwc")


def _mode_dp_nhwc(devices) -> TraceTarget:
    """tau=1 GSPMD DP with channels-last activations: same comm contract
    as dp (weights never reorient, so the grad all-reduce budget is
    byte-identical), plus the layout census pinning zero interior
    rank-4 transposes in the lowered step."""
    return _trainer_target("dp_nhwc", "cifar10_quick",
                           _data_mesh(devices), layout="nhwc")


def _mode_dp_bf16(devices) -> TraceTarget:
    return _trainer_target("dp_bf16", "cifar10_quick", _data_mesh(devices),
                           compute_dtype=jnp.bfloat16)


def _banked_remat_policy(family: str = "cifar10_quick",
                         dtype: str = "f32") -> str:
    """The bytes-minimal remat policy the schedule search banked in
    ``docs/byte_contracts/remat_policy.json`` for (family, dtype) —
    the remat twins route THIS policy so the banked graph+mem
    manifests pin the very schedule ``Config.remat`` would run.
    Deterministic ``"full"`` fallback when the table is absent or
    predates the family (first bank of a fresh clone)."""
    import json
    import pathlib

    from sparknet_tpu.analysis.byte_model import selected_policy

    path = (pathlib.Path(__file__).resolve().parents[2]
            / "docs" / "byte_contracts" / "remat_policy.json")
    try:
        table = json.loads(path.read_text())
    except (OSError, ValueError):
        return "full"
    return selected_policy(table, family, dtype, default="full")


def _mode_solo_remat(devices) -> TraceTarget:
    """The rematerialization twin of solo: same family/batch/layout,
    the loss built under the banked bytes-minimal ``Config.remat``
    policy (solvers/solver.py apply_remat).  The banked mem manifest
    is the proof obligation for the byte model's modeled
    saved-activation drop — remat changes residency, never the
    zero-collective comm contract."""
    return _mode_solo(devices, name="solo_remat",
                      remat=_banked_remat_policy())


def _mode_dp_remat(devices) -> TraceTarget:
    """tau=1 GSPMD DP under the banked remat policy: the comm contract
    is dp's exactly (recompute changes what the backward reads, not
    what the mesh reduces — the grad all-reduce moves the same param
    bytes), plus the mem twin pinning the residency drop at width 8."""
    return _trainer_target("dp_remat", "cifar10_quick",
                           _data_mesh(devices),
                           remat=_banked_remat_policy())


def _banked_act_policy(family: str = "cifar10_quick") -> str:
    """The bytes-minimal SAFE activation-storage policy the numcheck
    mixed-precision search banked in ``docs/num_contracts/
    mixed_policy.json`` for ``family`` — the act twins route THIS
    policy so the banked graph+mem+byte manifests pin the very
    schedule ``Config.activation_dtype`` would run.  Deterministic
    ``"blocks"`` fallback when the table is absent or predates the
    family (first bank of a fresh clone; matches the common.py
    ``"bf16" -> "blocks"`` alias)."""
    import json
    import pathlib

    from sparknet_tpu.analysis.num_model import selected_act_policy

    path = (pathlib.Path(__file__).resolve().parents[2]
            / "docs" / "num_contracts" / "mixed_policy.json")
    try:
        table = json.loads(path.read_text())
    except (OSError, ValueError):
        return "blocks"
    return selected_act_policy(table, family, default="blocks")


def _mode_solo_act_bf16(devices) -> TraceTarget:
    """The activation-storage twin of solo: same family/batch/layout,
    the forward built under the banked ``Config.activation_dtype``
    policy — bf16 at the storage boundaries, every layer upcasting to
    f32 before compute (accumulation stays f32, the numcheck
    contract).  Storage changes residency and step bytes, never the
    zero-collective comm contract."""
    return _mode_solo(devices, name="solo_act_bf16",
                      act=_banked_act_policy())


def _mode_dp_act_bf16(devices) -> TraceTarget:
    """tau=1 GSPMD DP under the banked activation-storage policy: the
    comm contract is dp's exactly (storage narrows what the backward
    READS, not what the mesh reduces — grads stay f32, the all-reduce
    moves the same param bytes), plus the mem/byte twins pinning the
    storage drop at width 8."""
    return _trainer_target("dp_act_bf16", "cifar10_quick",
                           _data_mesh(devices),
                           act=_banked_act_policy())


def _mode_mobilenet_dp(devices) -> TraceTarget:
    return _trainer_target("mobilenet_dp", "mobilenet", _data_mesh(devices))


def _mode_tau(devices) -> TraceTarget:
    return _trainer_target("tau", "cifar10_quick", _data_mesh(devices),
                           tau=3)


# the banked elastic widths: the manifests must show the SAME comm/HBM
# contract shape across mesh re-formation (ISSUE 8 — the tau-averaging
# round is width-invariant by design; these twins prove the lowered
# programs agree)
ELASTIC_WIDTHS = (8, 6, 4)


def _mode_elastic(devices, width: int) -> TraceTarget:
    """Width-parameterized elastic twin: the weighted τ-averaging round
    (``parallel/elastic.py``) lowered at mesh width ``width`` — the
    generalization of the fixed-mode sweep to parameterized mesh
    shapes.  Carry/donation/comm contracts match the tau mode's, plus
    the per-worker staleness-weight vector rides as a non-carry arg."""
    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
    from sparknet_tpu.parallel.elastic import ElasticTrainer
    from sparknet_tpu.solvers.solver import Solver

    if width > len(devices):
        raise RuntimeError(
            f"elastic_w{width} needs {width} devices, got {len(devices)}")
    family = GRAPH_SWEEP_FAMILIES["cifar10_quick"]
    per_device, tau = 2, 2
    solver = Solver(family.solver(), family.net(per_device))
    trainer = ElasticTrainer(solver, width=width, tau=tau,
                             devices=devices[:width])
    rs = np.random.RandomState(0)
    feeds_np = trainer._round_feeds(
        lambda g: _feeds_for(family, per_device,
                             np.random.RandomState(g % 97)), width)
    feeds = trainer._place_feeds(feeds_np, trainer.mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    weights = jax.device_put(
        jnp.ones((width,), jnp.float32),
        NamedSharding(trainer.mesh, P("data")))
    args = (trainer.variables, trainer.slots, weights, 0, feeds,
            solver._key)
    alt = args[:3] + (1,) + args[4:]
    carry_out = sum(len(jax.tree_util.tree_leaves(t)) for t in args[:2])
    return TraceTarget(
        name=f"elastic_w{width}",
        fn=trainer._program(width),
        args=args,
        alt_args=alt,
        meta={"family": "cifar10_quick", "mesh": {"data": width},
              "tau": tau, "batch": per_device * width, "dtype": "f32",
              "layout": "nchw", "elastic": True},
        param_bytes=_tree_bytes(solver.variables.params),
        state_bytes=_tree_bytes(solver.variables.state),
        carry_argnums=(0, 1),
        carry_out_leaves=carry_out,
    )


def _mode_easgd(devices) -> TraceTarget:
    return _trainer_target("easgd", "cifar10_quick", _data_mesh(devices),
                           tau=2, elastic_alpha=0.9 / len(devices))


def _mode_tp(devices) -> TraceTarget:
    from sparknet_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(num_devices=len(devices), model_parallel=2)
    return _trainer_target("tp", "lenet", mesh,
                           expects_sharded_params=True)


def _mode_sp(devices) -> TraceTarget:
    from sparknet_tpu.parallel.mesh import auto_mesh
    from sparknet_tpu.parallel.sharding import ShardingRules

    mesh = auto_mesh(num_devices=len(devices), seq_parallel=4)
    return _trainer_target(
        "sp", "transformer", mesh,
        rules=ShardingRules(attention_impl="ulysses"),
    )


def _mode_gpipe(devices) -> TraceTarget:
    """GPipe microbatch schedule (dryrun mode 5 shape): forward-only
    stage pipeline — the ppermute activation hops are the contract."""
    from jax.sharding import Mesh

    from sparknet_tpu.parallel.pipeline import pipeline_blocks, \
        stack_stage_params

    mesh = Mesh(np.array(devices), ("stage",))
    rs = np.random.RandomState(0)
    D = 16
    stacked = stack_stage_params([
        {"w": jnp.asarray(rs.randn(D, D) * 0.3, jnp.float32)}
        for _ in range(len(devices))
    ])
    blk = lambda p, a: jnp.tanh(a @ p["w"])
    xs = jnp.asarray(rs.randn(2 * len(devices), 4, D), jnp.float32)
    fn = jax.jit(lambda st, x: pipeline_blocks(mesh, blk, st, x))
    return TraceTarget(
        name="gpipe", fn=fn, args=(stacked, xs),
        meta={"family": "toy_blocks", "mesh": dict(mesh.shape),
              "tau": 1, "batch": int(xs.shape[0]), "dtype": "f32"},
        param_bytes=_tree_bytes(stacked), state_bytes=0,
    )


def _mode_moe(devices) -> TraceTarget:
    """Expert-parallel top-1 MoE token dispatch (dryrun mode 6 shape):
    the two all_to_alls (scatter out, gather back) are the contract."""
    from jax.sharding import Mesh

    from sparknet_tpu.parallel.expert import expert_parallel_moe

    mesh = Mesh(np.array(devices), ("expert",))
    rs = np.random.RandomState(0)
    E, D, H = len(devices), 16, 32
    params = tuple(
        jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
        for s in [(E, D), (E, H, D), (E, H), (E, D, H), (E, D)]
    )
    toks = jnp.asarray(rs.randn(8 * E, D), jnp.float32)
    fn = jax.jit(partial(expert_parallel_moe, mesh,
                         capacity_factor=float(E)))
    return TraceTarget(
        name="moe", fn=fn, args=(params, toks),
        meta={"family": "toy_moe", "mesh": dict(mesh.shape),
              "tau": 1, "batch": int(toks.shape[0]), "dtype": "f32"},
        param_bytes=_tree_bytes(params), state_bytes=0,
    )


def _mode_serve(devices, bucket: int) -> TraceTarget:
    """Bucket-parameterized serving twin (ISSUE 10): the EXACT forward
    program the engine AOT-compiles for one ladder bucket
    (``serve/engine.build_serve_program`` — TEST phase, end-bounded at
    the score blob, no loss/accuracy tail).  Single chip, forward-only:
    zero collectives, no carry (requests are stateless), and the
    alt-args lowering pins shape-stable tracing — a bucket program that
    recompiled per request would pay a compile on every flush."""
    from sparknet_tpu.serve.engine import build_serve_program, exec_batch

    fn, variables, feeds, alt_feeds = build_serve_program(
        "cifar10_quick", bucket)
    return TraceTarget(
        name=f"serve_b{bucket}", fn=fn,
        args=(variables, feeds),
        alt_args=(variables, alt_feeds),
        meta={"family": "cifar10_quick", "mesh": {}, "tau": 1,
              "batch": exec_batch(bucket), "dtype": "f32",
              "layout": "nchw", "serve": True,
              "serve_bucket": int(bucket)},
        param_bytes=_tree_bytes(variables.params),
        state_bytes=_tree_bytes(variables.state),
    )


SERVE_REPLICA_WIDTHS = (1, 2, 4)


def _mode_serve_replica(devices, width: int) -> TraceTarget:
    """Width-parameterized pod-serving twin (ISSUE 13): K replica
    copies of the transformer steady-state bucket forward (b64) as ONE
    data-sharded program over ``sized_data_mesh(width)`` — params
    REPLICATED (every replica serves the same weights, serve/router.py
    copies them on join), feeds sharded along the batch axis (each
    replica's bucket rides its own mesh device).  Serving is
    embarrassingly parallel: the comm contract is ZERO collectives at
    every width (a collective here would mean a replica's forward
    depends on another's traffic — the lowering bug the twins exist to
    catch).  The alt-args lowering pins shape-stable tracing exactly
    like the serve_b* twins."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.parallel.mesh import sized_data_mesh
    from sparknet_tpu.serve.engine import (
        _end_layer, _family, _forward_fn, _score_blob,
        _synthetic_feeds, exec_batch)

    if width > len(devices):
        raise RuntimeError(
            f"serve_r{width} needs {width} devices, got {len(devices)}")
    mesh = sized_data_mesh(width, devices=devices)
    family = _family("transformer")
    batch = width * exec_batch(64)
    network = Network(family.net(batch), Phase.TEST)
    variables = jax.device_put(network.init(jax.random.key(0)),
                               NamedSharding(mesh, P()))
    blob = _score_blob(network)
    fn = jax.jit(_forward_fn(network, blob, _end_layer(network, blob)))

    def _place(seed: int):
        sharding = NamedSharding(mesh, P("data"))
        return {k: jax.device_put(jnp.asarray(v), sharding)
                for k, v in _synthetic_feeds(family, batch,
                                             seed).items()}

    return TraceTarget(
        name=f"serve_r{width}", fn=fn,
        args=(variables, _place(0)),
        alt_args=(variables, _place(1)),
        meta={"family": "transformer", "mesh": {"data": width},
              "tau": 1, "batch": batch, "dtype": "f32",
              "layout": "nchw", "serve": True, "serve_bucket": 64,
              "replicas": width},
        param_bytes=_tree_bytes(variables.params),
        state_bytes=_tree_bytes(variables.state),
    )


DECODE_OCCUPANCIES = (1, 4)


def _mode_decode_paged(devices, occupancy: int) -> TraceTarget:
    """Occupancy-parameterized paged-decode twin (ISSUE 19): the EXACT
    cached per-token step the ``PagedDecoder`` AOT-compiles
    (``serve/paged.build_decode_program`` — one token per slot row,
    K/V written through the block tables, attention via the block
    gather).  Occupancy changes only the DATA (live tables/positions),
    never a shape, so every occupancy twin must lower byte-identical —
    that IS the shape-stability contract behind zero post-warmup
    compiles at any admission churn.  Single chip, zero collectives;
    the K/V pools are the carry (donated, returned first)."""
    from sparknet_tpu.serve.paged import build_decode_program

    fn, args, alt_args, meta = build_decode_program(occupancy)
    return TraceTarget(
        name=f"decode_paged_o{occupancy}", fn=fn,
        args=args, alt_args=alt_args, meta=meta,
        param_bytes=_tree_bytes(args[0].params),
        state_bytes=_tree_bytes(args[0].state),
        carry_argnums=(1, 2), carry_out_leaves=2,
    )


def _mode_decode_rect(devices) -> TraceTarget:
    """The rectangle decode baseline (serve/continuous.py): the full
    [slots, seq_len] forward the cacheless ``ContinuousDecoder`` pays
    on EVERY emitted token — banked so the byte model prices the
    paged-vs-rectangle A/B from manifests alone.  No carry (the
    rectangle holds no device state between steps; that is the
    point)."""
    from sparknet_tpu.serve.paged import build_rect_program

    fn, variables, feeds, alt_feeds = build_rect_program()
    return TraceTarget(
        name="decode_rect", fn=fn,
        args=(variables, feeds),
        alt_args=(variables, alt_feeds),
        meta={"family": "charlm", "mesh": {}, "tau": 1,
              "batch": int(feeds["data"].shape[0]), "dtype": "f32",
              "layout": "nchw", "serve": True, "decode": "rect"},
        param_bytes=_tree_bytes(variables.params),
        state_bytes=_tree_bytes(variables.state),
    )


MODES: dict[str, Callable] = {
    "solo": _mode_solo,
    "solo_nhwc": _mode_solo_nhwc,
    "solo_remat": _mode_solo_remat,
    "solo_act_bf16": _mode_solo_act_bf16,
    "dp": _mode_dp,
    "dp_nhwc": _mode_dp_nhwc,
    "dp_remat": _mode_dp_remat,
    "dp_act_bf16": _mode_dp_act_bf16,
    "dp_bf16": _mode_dp_bf16,
    "tau": _mode_tau,
    "easgd": _mode_easgd,
    "tp": _mode_tp,
    "sp": _mode_sp,
    "gpipe": _mode_gpipe,
    "moe": _mode_moe,
    "mobilenet_dp": _mode_mobilenet_dp,
}

# width-parameterized elastic twins (the fixed-mode registry generalized
# to parameterized mesh shapes): one registered mode per banked width
MODES.update({
    f"elastic_w{w}": partial(_mode_elastic, width=w)
    for w in ELASTIC_WIDTHS
})

# bucket-parameterized serving twins: one per AOT ladder bucket, so the
# graph+mem contracts pin the very programs the engine serves
from sparknet_tpu.serve.engine import SERVE_BUCKETS  # noqa: E402

MODES.update({
    f"serve_b{b}": partial(_mode_serve, bucket=b)
    for b in SERVE_BUCKETS
})

# replica-width pod-serving twins (ISSUE 13): the K-copy steady-state
# forward per banked width — zero collectives at every width
MODES.update({
    f"serve_r{w}": partial(_mode_serve_replica, width=w)
    for w in SERVE_REPLICA_WIDTHS
})

# occupancy-parameterized paged-decode twins (ISSUE 19) + the rectangle
# baseline: equal-program-at-every-occupancy is the banked contract
MODES.update({
    f"decode_paged_o{o}": partial(_mode_decode_paged, occupancy=o)
    for o in DECODE_OCCUPANCIES
})
MODES["decode_rect"] = _mode_decode_rect


def list_modes() -> list[str]:
    return list(MODES)


def build_target(name: str, n_devices: int = 8) -> TraceTarget:
    """Build one mode's traceable target on the first ``n_devices``
    visible devices.  Caller (graphcheck) is responsible for having
    pinned the CPU platform and forced the virtual device count."""
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"mode {name!r} needs {n_devices} devices, found "
            f"{len(devices)}; launch with "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_devices} JAX_PLATFORMS=cpu")
    return MODES[name](devices[:n_devices])
