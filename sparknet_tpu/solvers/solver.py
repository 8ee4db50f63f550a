"""Solver: the training-step driver around the jit-compiled net.

TPU-native redesign of Caffe's Solver/SGDSolver scaffolding (ref:
caffe/src/caffe/solver.cpp: Step :193-282, Solve :285-326, TestAndStoreResult
:414-444, Snapshot/Restore :447-519).  The entire per-iteration pipeline —
iter_size gradient accumulation, LR policy, clipping, regularization, the
optimizer rule, and the parameter update — is ONE jitted XLA program; the
Python loop only feeds data and reads the smoothed loss.  Compare the
reference's per-iter host round trips (callback feed + float-by-float JNA
weight IO, ref: Net.scala:131-171) — on TPU the weights never leave HBM.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.common import Phase, get_config, root_key, step_key
from sparknet_tpu.compiler.graph import Network, NetVars
from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import Span
from sparknet_tpu.ops.attention import AttentionLayer
from sparknet_tpu.ops.moe import CAPACITY_TILE, live_tiles
from sparknet_tpu.proto.text_format import Message, parse_file, serialize
from sparknet_tpu.solvers.lr_policy import learning_rate
from sparknet_tpu.solvers.updates import apply_update, init_slots
from sparknet_tpu.utils.profiling import (account_compiled, hbm_live,
                                          step_span)

# device scope of the optimizer update inside every jitted train step.
# Not an ``L.`` scope: a trace reader books ``L.<name>`` as a net layer
UPDATE_SCOPE = "S.update"

# enum (2015) and string (modern) solver types both accepted
_TYPE_ALIASES = {
    "SGD": "SGD",
    "NESTEROV": "Nesterov",
    "ADAGRAD": "AdaGrad",
    "RMSPROP": "RMSProp",
    "ADADELTA": "AdaDelta",
    "ADAM": "Adam",
    "Nesterov": "Nesterov",
    "AdaGrad": "AdaGrad",
    "RMSProp": "RMSProp",
    "AdaDelta": "AdaDelta",
    "Adam": "Adam",
    "ADAMW": "AdamW",
    "AdamW": "AdamW",
}


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Typed view of SolverParameter (ref: caffe.proto:102-308)."""

    base_lr: float = 0.01
    lr_policy: str = "fixed"
    gamma: float = 0.1
    power: float = 0.75
    stepsize: int = 100000
    stepvalue: tuple = ()
    max_iter: int = 100000
    momentum: float = 0.0
    momentum2: float = 0.999
    rms_decay: float = 0.99
    delta: float = 1e-8
    weight_decay: float = 0.0
    regularization_type: str = "L2"
    clip_gradients: float = -1.0
    iter_size: int = 1
    solver_type: str = "SGD"
    # TPU-native memory knob: rematerialize the forward under grad
    # (jax.checkpoint) — trades FLOPs for HBM on activation-heavy nets.
    # No reference counterpart; Caffe holds all activations resident.
    remat: bool = False
    random_seed: int = -1
    test_iter: tuple = ()
    # one stage-tuple per test net (ref: SolverParameter.test_state +
    # Solver::InitTestNets solver.cpp:135-190 NetState merge); () = one
    # default test net with no stages.  test_levels holds the matching
    # NetState.level per test net (0 when unspecified).
    test_states: tuple = ()
    test_levels: tuple = ()
    test_interval: int = 0
    display: int = 0
    average_loss: int = 1
    snapshot: int = 0
    snapshot_prefix: str = ""
    snapshot_after_train: bool = True
    # BINARYPROTO -> <prefix>.caffemodel, HDF5 -> <prefix>.caffemodel.h5
    # written alongside the solver state (ref: Solver::Snapshot
    # solver.cpp:447-466 model + state pair); "" skips the model file
    snapshot_format: str = "BINARYPROTO"
    # per-iteration per-layer forward/param/grad abs-mean diagnostics
    # (ref: SolverParameter.debug_info + Net::ForwardDebugInfo /
    # BackwardDebugInfo, net.cpp:658-735) — computed in-graph as cheap
    # reductions, printed each iteration
    debug_info: bool = False

    @classmethod
    def from_proto(cls, m: Message) -> "SolverConfig":
        stype = m.get_str("type", m.get_str("solver_type", "SGD"))
        if stype not in _TYPE_ALIASES:
            raise ValueError(
                f"unknown solver type {stype!r}; expected one of "
                f"{sorted(set(_TYPE_ALIASES.values()))} "
                "(ref: SolverRegistry::CreateSolver fails on unknown types)"
            )
        return cls(
            base_lr=m.get_float("base_lr", 0.01),
            lr_policy=m.get_str("lr_policy", "fixed"),
            gamma=m.get_float("gamma", 0.1),
            power=m.get_float("power", 0.75),
            stepsize=m.get_int("stepsize", 100000),
            stepvalue=tuple(int(v) for v in m.get_all("stepvalue")),
            max_iter=m.get_int("max_iter", 100000),
            momentum=m.get_float("momentum", 0.0),
            momentum2=m.get_float("momentum2", 0.999),
            rms_decay=m.get_float("rms_decay", 0.99),
            delta=m.get_float("delta", 1e-8),
            weight_decay=m.get_float("weight_decay", 0.0),
            regularization_type=m.get_str("regularization_type", "L2"),
            clip_gradients=m.get_float("clip_gradients", -1.0),
            iter_size=m.get_int("iter_size", 1),
            solver_type=_TYPE_ALIASES[stype],
            random_seed=m.get_int("random_seed", -1),
            test_iter=tuple(int(v) for v in m.get_all("test_iter")),
            test_states=tuple(
                tuple(str(s) for s in ts.get_all("stage"))
                for ts in m.get_all("test_state")
            ),
            test_levels=tuple(
                ts.get_int("level", 0) for ts in m.get_all("test_state")
            ),
            test_interval=m.get_int("test_interval", 0),
            display=m.get_int("display", 0),
            average_loss=m.get_int("average_loss", 1),
            snapshot=m.get_int("snapshot", 0),
            snapshot_prefix=m.get_str("snapshot_prefix", ""),
            snapshot_after_train=m.get_bool("snapshot_after_train", True),
            snapshot_format=m.get_str("snapshot_format", "BINARYPROTO"),
            debug_info=m.get_bool("debug_info", False),
        )


def load_solver_net(solver_msg: Message, root: str = "") -> Message:
    """Resolve the net referenced by a solver prototxt
    (ref: Solver::InitTrainNet's net/net_param/train_net/train_net_param
    precedence, solver.cpp:66-108)."""
    for field in ("net_param", "train_net_param"):
        if solver_msg.has(field):
            return solver_msg.get_msg(field)
    for field in ("net", "train_net"):
        if solver_msg.has(field):
            path = solver_msg.get_str(field)
            if root and not os.path.isabs(path):
                path = os.path.join(root, path)
            return parse_file(path)
    raise ValueError("solver prototxt declares no net")


DataFn = Callable[[int], dict[str, Any]]  # iteration -> feed dict


def remat_policy(cfg: SolverConfig) -> str:
    """The effective rematerialization policy for a step build.

    Two knobs merge here: the per-solver prototxt bool
    (``SolverConfig.remat`` — the pre-existing coarse switch, mapped to
    the ``"full"`` policy it always meant) and the global
    ``Config.remat`` string (``SPARKNET_REMAT`` / ``set_config`` — the
    bytecheck schedule search's routing, ``docs/byte_contracts/
    remat_policy.json``).  Empty string = off; with both knobs off
    the step builder below lowers to the banked graph/mem manifests
    (the bit-identity pin in tests/test_bytecheck.py)."""
    if cfg.remat:
        return "full"
    return get_config().remat


def apply_remat(loss_fn, policy: str):
    """Wrap ``loss_fn`` in ``jax.checkpoint`` under ``policy``:
    ``""``/``"none"`` = untouched (the off path returns the SAME
    function object — zero trace perturbation), ``"full"`` = nothing
    saveable (plain ``jax.checkpoint``), ``"dots"`` = dots_saveable
    (matmul outputs kept, convs recomputed), ``"blocks"`` = save only
    the pooling-boundary activations ``Network.apply`` tags with
    ``checkpoint_name`` when ``Config.remat == "blocks"``
    (compiler/graph.py BLOCK_SAVE_NAME)."""
    if not policy or policy == "none":
        return loss_fn
    if policy == "full":
        return jax.checkpoint(loss_fn)
    from jax import checkpoint_policies as _cp

    if policy == "dots":
        return jax.checkpoint(loss_fn, policy=_cp.dots_saveable)
    if policy == "blocks":
        from sparknet_tpu.compiler.graph import BLOCK_SAVE_NAME

        return jax.checkpoint(
            loss_fn, policy=_cp.save_only_these_names(BLOCK_SAVE_NAME))
    raise ValueError(f"unknown remat policy {policy!r} "
                     "(want '', 'full', 'dots', or 'blocks')")


def build_train_step(cfg: SolverConfig, net: Network, specs,
                     debug: bool = False):
    """The train step as a module-level builder:
    ``step(variables, slots, it, feeds, key) -> (variables, slots,
    loss)`` (plus a stats dict in debug mode).

    Factored out of :class:`Solver` so consumers that must not
    materialize a training state can build the SAME program the Solver
    jits — the memcheck batch-fit solver traces this abstractly
    (``jax.make_jaxpr`` over :func:`abstract_train_state` structs, no
    arrays) to price a family's memory footprint, and its donation
    accounting credits exactly the argnums-(0, 1) carry the Solver
    donates below.  ``debug=None`` is not accepted here: the Solver
    wrapper owns the config-following default."""

    def loss_fn(params, state, feeds, rng):
        # execution-time capture only in debug mode: the reductions
        # are cheap but extra outputs would defeat fusion otherwise
        sink: dict = {} if debug else None
        _, new_state, loss = net.apply(
            NetVars(params=params, state=state), feeds, rng=rng,
            debug_sink=sink,
        )
        return loss, (new_state, sink if debug else {})

    loss_fn = apply_remat(loss_fn, remat_policy(cfg))

    def train_step(variables, slots, it, feeds, key):
        rng = step_key(key, it)
        if cfg.iter_size > 1:
            # scan over micro-batches accumulating grads (ref: iter_size
            # accumulation, solver.cpp:221-224 + Normalize)
            def body(carry, micro):
                gsum, state, lsum, k = carry
                (loss, (new_state, fwd)), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(variables.params, state, micro, k)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                return (
                    (gsum, new_state, lsum + loss, jax.random.fold_in(k, 1)),
                    fwd,  # debug: per-micro-batch means, last one shown
                )

            zero_g = jax.tree_util.tree_map(jnp.zeros_like, variables.params)
            (grads, new_state, loss_sum, _), fwd_seq = jax.lax.scan(
                body, (zero_g, variables.state, 0.0, rng), feeds
            )
            loss = loss_sum / cfg.iter_size
            fwd = jax.tree_util.tree_map(lambda a: a[-1], fwd_seq)
        else:
            (loss, (new_state, fwd)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(variables.params, variables.state, feeds, rng)
        rate = learning_rate(cfg, it)
        with jax.named_scope(UPDATE_SCOPE):
            new_params, new_slots = apply_update(
                cfg, variables.params, grads, slots, specs, rate, it
            )
        out = NetVars(params=new_params, state=new_state), new_slots, loss
        if not debug:
            return out
        stats = {
            "forward": fwd,
            "param": {
                f"{ln}[{i}]": jnp.mean(jnp.abs(p))
                for ln, plist in variables.params.items()
                for i, p in enumerate(plist) if p.size
            },
            "diff": {
                f"{ln}[{i}]": jnp.mean(jnp.abs(g))
                for ln, glist in grads.items()
                for i, g in enumerate(glist) if g.size
            },
        }
        return (*out, stats)

    return train_step


# signature of a fresh state -> its jitted ``key -> (variables, slots)``;
# the few nets of a job, bounded (a closure keeps the net it was made for)
_FRESH_STATES: collections.OrderedDict = collections.OrderedDict()
_FRESH_STATES_HELD = 16


def fresh_train_state(cfg: SolverConfig, net: Network,
                      feed_shapes: dict[str, tuple] | None = None,
                      feed_dtypes: dict[str, Any] | None = None):
    """The jitted ``key -> (variables, slots)`` of a fresh training state:
    ``net.init`` (shape inference and every filler) and the optimizer's
    zero slots inside ONE trace, so a ``Solver`` is born in one program
    where an eager init ran one per filler and shape.  The PRNG key is
    the program's only argument (a closed-over key would compile anew a
    seed); the prototxt, the phase view, the feed shapes and dtypes, the
    framework ``Config`` and the solver type are the signature it is
    kept under, so equal nets share one callable and one executable
    whatever their seeds.  The values are the eager call's, to the bit
    for every zoo net's fillers (``ops/fillers.py`` fences its normal
    sampler for that; where a backend fuses a layer's own product and
    sum, within ulps: ``tests/test_fresh_state.py`` names the places)."""
    def fresh(key):
        variables = net.init(key, feed_shapes, feed_dtypes)
        return variables, init_slots(cfg.solver_type, variables.params)

    shapes = {**net.feed_shapes(), **(feed_shapes or {})}
    signature = (
        serialize(net.net_param), net.phase, net.batch_override,
        tuple((type(l), serialize(l.lp)) for l in net.layers),
        tuple(sorted((k, tuple(v)) for k, v in shapes.items())),
        tuple(sorted((k, jnp.dtype(v).name)
                     for k, v in (feed_dtypes or {}).items())),
        dataclasses.replace(get_config(), seed=0), cfg.solver_type,
    )
    held = _FRESH_STATES.pop(signature, None)
    if held is None:
        held = jax.jit(fresh)
    else:
        # an equal net came before: the executable is its, but what
        # ``init`` notes on THIS net's layers as it learns their shapes
        # (``blob_info``, a scan's chunk) takes a trace of its own
        jax.eval_shape(fresh, _key_struct())
    _FRESH_STATES[signature] = held  # the newest last
    while len(_FRESH_STATES) > _FRESH_STATES_HELD:
        _FRESH_STATES.popitem(last=False)
    return held


def _key_struct() -> jax.ShapeDtypeStruct:
    """The aval of :func:`common.root_key`'s key, so an abstract state
    and a ``Solver`` meet the same trace."""
    return jax.eval_shape(jax.random.key, 0)


def abstract_train_state(cfg: SolverConfig, net: Network):
    """``(variables, slots)`` of a fresh training state as
    ``ShapeDtypeStruct`` pytrees: ``jax.eval_shape`` of the very callable
    a ``Solver`` runs (:func:`fresh_train_state`: one definition of what
    a fresh state is), so nothing materializes (vgg16's half-gigabyte of
    params stays abstract).  The memcheck batch-fit solver builds its
    footprint model from these."""
    return jax.eval_shape(fresh_train_state(cfg, net), _key_struct())


class Solver:
    """Drives training/eval of a prototxt-defined net.

    ``data_fn(it)`` supplies the train feed dict for iteration ``it``
    (with iter_size>1: arrays carry a leading [iter_size] axis and the
    jitted step scans over micro-batches, ref: solver.cpp:221-224).

    Construction runs ONE program for the fresh state
    (:func:`fresh_train_state`: every filler and the optimizer's zero
    slots), keyed by the net's shape and read by the key of
    ``random_seed``: a second solver of an equal net, whatever its seed,
    compiles nothing, and a new process finds the executable in jax's
    persistent cache.
    """

    def __init__(
        self,
        solver: Message | SolverConfig,
        net_param: Message,
        feed_shapes: dict[str, tuple] | None = None,
        feed_dtypes: dict[str, Any] | None = None,
        batch_override: int | None = None,
    ):
        # sn.solver.build: the whole construction, unfenced (a sync here
        # would be one the program does not have): what init left running
        # on the device is booked to whatever blocks next.  Like the
        # fences, the set-up spans are no journal lines (no Recorder)
        with Span(None, "sn.solver.build", host=True, compile_stats=True):
            self.config = (
                solver if isinstance(solver, SolverConfig) else SolverConfig.from_proto(solver)
            )
            fmt = self.config.snapshot_format.upper()
            if fmt not in ("", "BINARYPROTO", "HDF5"):
                # fail at construction, not hours later at the first snapshot
                raise ValueError(
                    f"unknown snapshot_format {self.config.snapshot_format!r} "
                    "(BINARYPROTO|HDF5|'')"
                )
            if fmt == "HDF5":
                try:
                    import h5py  # noqa: F401
                except ImportError as e:
                    raise ValueError(
                        "snapshot_format=HDF5 needs h5py (pip install "
                        "sparknet-tpu[hdf5])"
                    ) from e
            self.net_param = net_param
            # sn.solver.nets: layer set-up of the train net and every test net
            with Span(None, "sn.solver.nets", host=True,
                      compile_stats=True) as sp:
                self.train_net = Network(net_param, Phase.TRAIN, batch_override)
                # one TEST net per test_state (ref: Solver::InitTestNets
                # solver.cpp:135-190: NetState per test net, merged stages);
                # no test_state = the single default test net
                states = self.config.test_states or ((),)
                levels = self.config.test_levels or (0,) * len(states)
                self.test_nets = [
                    Network(net_param, Phase.TEST, batch_override,
                            stages=set(st), level=lv)
                    for st, lv in zip(states, levels)
                ]
                self.test_net = self.test_nets[0]
                nets = [self.train_net, *self.test_nets]
                sp.set(nets=len(nets),
                       layers=sum(len(n.layers) for n in nets))
            # ref: Solver::InitTestNets CHECK_EQ(test_iter size, num test nets)
            if self.config.test_iter and len(self.config.test_iter) != len(
                self.test_nets
            ):
                raise ValueError(
                    f"test_iter specifies {len(self.config.test_iter)} counts "
                    f"but there are {len(self.test_nets)} test nets "
                    "(one test_iter per test net, ref: solver.cpp:113-118)"
                )
            seed = self.config.random_seed if self.config.random_seed >= 0 else None
            self._key = root_key(seed)
            # sn.solver.init: the fresh state's ONE program (shape
            # inference and the fillers, the optimizer's slots: a trace
            # and a compile or a cache load, then its run), the
            # per-parameter specs
            with Span(None, "sn.solver.init", host=True,
                      compile_stats=True) as sp:
                variables, slots = fresh_train_state(
                    self.config, self.train_net, feed_shapes, feed_dtypes
                )(self._key)
                # a jitted call hands its dicts back sorted by key (as
                # ``init_slots``'s tree_map always did); until the first
                # step does the same the variables stay in the net's
                # layer order, as ``net.init`` builds them (a snapshot or
                # a weight export at iteration 0 walks them)
                in_order = lambda d: {
                    layer.name: d[layer.name]
                    for layer in self.train_net.layers if layer.name in d}
                self.variables = NetVars(params=in_order(variables.params),
                                         state=in_order(variables.state))
                self.slots = slots
                self.iter = 0
                self.smoothed_loss = 0.0
                self._loss_window: list[float] = []
                # obs bookkeeping (sparknet_tpu/obs): both stay inert — and the
                # jitted programs bit-identical — while SPARKNET_OBS is off
                self._obs_in_step = False
                self._obs_images_per_iter = 0
                self._specs = self.train_net.param_specs_for(self.variables)
                # programs: the executables the init asks for (the span's
                # compiles / cache_hits say how that one was come by)
                sp.set(programs=1, params=sum(
                    int(p.size) for ps in self.variables.params.values()
                    for p in ps))
            # Donate the (variables, slots) carry: step() rebinds both from
            # the outputs every iteration, so keeping the inputs alive just
            # holds a second copy of params+slots in device memory (the
            # graphcheck donation audit flagged exactly this; the trainer
            # and jitted_train_step paths already donated).  Callers that
            # need the pre-step buffers use jitted_train_step(donate=False).
            self._train_step = jax.jit(self._make_train_step(),
                                       donate_argnums=(0, 1))
            # jitted step -> the entries of its jit cache whose HBM account
            # a span carries (``account_compiled``); the devices whose live
            # bytes the fences read
            self._accounted: dict = {}
            leaves = jax.tree_util.tree_leaves(self.variables)
            self._devices = list(leaves[0].devices()) if leaves else []
            self._eval_steps = [
                jax.jit(self._make_eval_step(net)) for net in self.test_nets
            ]
            self._eval_step = self._eval_steps[0]

    # ------------------------------------------------------------------
    def _make_train_step(self, debug: bool | None = None):
        """:func:`build_train_step` over this solver's config, train net
        and specs.  ``debug=None`` follows ``config.debug_info``; pass
        ``False`` for consumers that require the plain 3-tuple contract
        (the distributed trainer packs its own feeds; the bench handle
        is a public API)."""
        debug = self.config.debug_info if debug is None else debug
        return build_train_step(self.config, self.train_net, self._specs,
                                debug)

    def _print_debug_info(self, stats) -> None:
        """Caffe's per-iteration diagnostic lines (ref: net.cpp:658-735
        ForwardDebugInfo / BackwardDebugInfo / UpdateDebugInfo): top-blob
        data abs-means at execution time (in-place layers included),
        param diff abs-means, param data abs-means."""
        stats = jax.device_get(stats)  # ONE transfer, not one per scalar
        for (layer, top), v in stats["forward"].items():
            print(
                f"    [Forward] Layer {layer}, top blob {top} "
                f"data: {float(v):.6g}"
            )
        for name, v in stats["diff"].items():
            print(
                f"    [Backward] Layer {name.split('[')[0]}, "
                f"param blob {name} diff: {float(v):.6g}"
            )
        for name, v in stats["param"].items():
            print(
                f"    [Update] Layer {name.split('[')[0]}, "
                f"param blob {name} data: {float(v):.6g}"
            )

    def _make_eval_step(self, net: Network):
        def eval_step(variables, feeds):
            blobs, _, _ = net.apply(variables, feeds, rng=None, train=False)
            return {name: blobs[name] for name in net.output_blobs() if name in blobs}

        return eval_step

    # ------------------------------------------------------------------
    def jitted_train_step(self, donate: bool = True):
        """Public handle for benchmarking/driving the train step:
        ``(fn, variables, slots, key)`` where
        ``fn(variables, slots, it, feeds, key) -> (variables, slots, loss)``.
        With ``donate=True`` the returned state buffers are donated on each
        call — thread the returned values, do not reuse ``self.variables``
        afterwards."""
        fn = jax.jit(
            self._make_train_step(debug=False),
            donate_argnums=(0, 1) if donate else (),
        )
        return fn, self.variables, self.slots, self._key

    # ------------------------------------------------------------------
    def jitted_scan_steps(self, n: int, donate: bool = True,
                          stacked_feeds: bool = False, step_fn=None):
        """``n`` full solver iterations fused into ONE device program via
        ``lax.scan`` — the TPU-native training loop (SURVEY §3: everything
        under jit is traced once; host dispatch is not free).

        Returns ``(fn, variables, slots, key)`` with
        ``fn(variables, slots, it0, feeds, key) -> (variables, slots,
        losses[n])``; iteration numbers ``it0 .. it0+n-1`` drive the lr
        schedule exactly as ``n`` separate calls would (ref: the per-iter
        ``GetLearningRate`` in solver.cpp:27-58 — same schedule, one
        dispatch).

        ``stacked_feeds=False``: every step consumes the same feed dict
        (the benchmark protocol's fixed in-memory batch).
        ``stacked_feeds=True``: each feed array carries a leading [n]
        axis and step ``i`` consumes slice ``i`` (real data: stage n
        minibatches, dispatch once).  ``step_fn``: an already-built
        per-step function to scan (ParallelTrainer reuses its own) —
        default builds a fresh one.
        """
        base_step = step_fn or self._make_train_step(debug=False)

        def multi(variables, slots, it0, feeds, key):
            def body(carry, x):
                variables, slots = carry
                if stacked_feeds:
                    i, micro = x
                else:
                    i, micro = x, feeds
                variables, slots, loss = base_step(
                    variables, slots, it0 + i, micro, key
                )
                return (variables, slots), loss

            xs = jnp.arange(n)
            if stacked_feeds:
                xs = (xs, feeds)
            (variables, slots), losses = jax.lax.scan(
                body, (variables, slots), xs
            )
            return variables, slots, losses

        fn = jax.jit(multi, donate_argnums=(0, 1) if donate else ())
        return fn, self.variables, self.slots, self._key

    # ------------------------------------------------------------------
    def step(self, num_iters: int, data_fn: DataFn, callback=None,
             scan_chunk: int = 1) -> float:
        """Run ``num_iters`` training iterations (ref: Solver::Step).

        Returns the final smoothed loss.  ``callback(iter, loss)`` runs
        every iteration on the host (display/snapshot hooks).

        ``scan_chunk > 1`` fuses that many iterations per device dispatch
        (lax.scan over staged minibatches — the TPU-native loop).  The
        chunk size is
        shrunk to divide the display and snapshot cadences so those fire
        at their exact reference iterations; callbacks then run in order
        AFTER each chunk (each still sees its per-iteration loss, but
        solver state has already advanced to the chunk end — interactive
        per-step control wants scan_chunk=1).  ``debug_info`` forces the
        per-iteration path (its stats are per-step host prints).

        With ``SPARKNET_OBS`` armed, one per-round obs record covers the
        whole call (wall fence-stamped on the final loss VALUE, per the
        round-5 contract); disabled, the body below runs byte-for-byte
        unchanged — same programs, same dispatch count."""
        rec = get_recorder()
        if not (rec and not self._obs_in_step and num_iters > 0):
            return self._step_impl(num_iters, data_fn, callback,
                                   scan_chunk)
        self._obs_in_step = True
        t0 = time.perf_counter()
        it0 = self.iter
        try:
            out = self._step_impl(num_iters, data_fn, callback,
                                  scan_chunk)
        finally:
            self._obs_in_step = False
        self._emit_obs_round(rec, it0, t0)
        return out

    def _step_impl(self, num_iters: int, data_fn: DataFn, callback=None,
                   scan_chunk: int = 1) -> float:
        """The body of :meth:`step` (see its docstring)."""
        cfg = self.config
        if scan_chunk > 1 and not cfg.debug_info:
            return self._step_scanned(num_iters, data_fn, callback,
                                      scan_chunk)
        for _ in range(num_iters):
            # sn.step: the data wait and the dispatch of one iteration
            with step_span("sn.step", self.iter) as sp:
                feeds = data_fn(self.iter)
                if self._obs_in_step:
                    self._obs_images_per_iter = self._feed_images(feeds)
                args = (self.variables, self.slots, self.iter, feeds,
                        self._key)
                out = self._train_step(*args)
                account_compiled(sp, self._accounted, self._train_step, *args)
            if cfg.debug_info:
                self.variables, self.slots, loss, stats = out
                self._print_debug_info(stats)
            else:
                self.variables, self.slots, loss = out
            # Keep losses as device arrays: blocking on float(loss) every
            # iteration would serialize host feed prep against device compute
            # (JAX async dispatch).  Materialize only at display/callback
            # boundaries.  Smoothing window per solver.cpp:235-257.
            self._loss_window.append(loss)
            if len(self._loss_window) > cfg.average_loss:
                self._loss_window.pop(0)
            self.iter += 1
            if cfg.display and self.iter % cfg.display == 0:
                print(
                    f"Iteration {self.iter}, loss = {self._smoothed():.6g}, "
                    f"lr = {float(learning_rate(cfg, self.iter)):.6g}"
                )
            if callback:
                with Span(None, "sn.step.fence", it=self.iter) as sp:
                    loss_val = sp.fence_value(float(loss))
                    sp.set(**hbm_live(self._devices))
                callback(self.iter, loss_val)
            if cfg.snapshot and self.iter % cfg.snapshot == 0 and cfg.snapshot_prefix:
                self.save(f"{cfg.snapshot_prefix}_iter_{self.iter}")
        # sn.step.fence: the host blocked on the device for the losses.
        # On the profiler's clock only (no Recorder): in the journal the
        # round record that step() closes on this value is its line
        with Span(None, "sn.step.fence", it=self.iter) as sp:
            self.smoothed_loss = sp.fence_value(self._smoothed())
            sp.set(**self._fence_stats(), **hbm_live(self._devices))
        return self.smoothed_loss

    def _fence_stats(self) -> dict:
        """What the net's layers kept of the last step in their state, for
        the fence's span; read after the fence, so it costs no device
        sync of its own, and empty for a net without such a layer.

        The MoE layers' ``load`` (tokens per expert): the fullest
        expert's tokens over the layers, and the (token, slot) pairs and
        experts of one layer, whose quotient is the mean.  Where a layer
        holds a share of its experts, or selects with a balancing bias:
        the layers counted, the pairs that landed on held experts over
        all of them, the sorted rows those layers moved that step
        (``moe_rows_moved``: whole tiles of 512, ``ops/moe.py
        live_tiles``, the count the device's loops ran, asked of the same
        ``load``), how many of the layers moved fewer rows than all
        their pairs (``moe_compact_layers``), and the bias's extremes.
        A loss layer that keeps
        its ``value`` (``loss_param { keep_value: true }``) gives it under
        the layer's name.  The selective-scan layers (``ops/ssm.py``)
        keep no state between steps: the fence names how many there are,
        how many of them run their scan as the Pallas kernels (the others
        as the ``lax.scan`` loop form: on the CPU all of them).  The
        gated-DeltaNet layers (``ops/linear_attention.py``) the same way:
        how many there are, and how many of them took the Pallas kernels
        at their last trace.  The attention layers (``ops/attention.py``)
        likewise: how many there are, and how many of them ran their core
        as the splash kernels at their last trace (the others in the XLA
        formulation or sequence-parallel: on the CPU none); where gated
        attention layers have a window (``swa_*``): how many of them, and
        of the causal (query block, key block) pairs of the windowed
        cores, at the width the core hands its kernels, the share that
        holds a key some query sees (``ops/attention.py window_blocks``:
        what a ``LocalMask`` leaves the kernels to visit), and how many of
        the windowed layers ran the backward that walks only those blocks
        and writes dq once at their last trace (``swa_band_layers``,
        ``ops/attention.py band_backward``: on the CPU none).  A net with a
        looped region
        (``compiler/graph.py LoopRegion``): the passes of the region
        (``ut_steps``) and, where the exit-weighted loss kept them, the
        mean cross-entropy of every pass (``ut_loss_<t>``, 1-based) and
        the mean exit step of the last step before the fence.

        Which path a layer took (``*_kernel_layers``, ``swa_band_layers``)
        is here because a silent fall-back shows nowhere else; what a path
        keeps for the backward is not: the step program's HBM account on
        its ``sn.step`` span measures that whole
        (``utils/profiling.step_account``)."""
        state = self.variables.state
        stats = {name: float(st["value"]) for name, st in state.items()
                 if "value" in st}
        if self.train_net.loops:
            stats["ut_steps"] = self.train_net.loops[0].count
            for st in state.values():
                if "step_loss" in st:
                    stats.update({f"ut_loss_{t}": float(v) for t, v in
                                  enumerate(np.asarray(st["step_loss"]), 1)})
                    stats["exit_mean_step"] = float(st["exit_mean_step"])
        scans = [l for l in self.train_net.layers if l.type == "Mamba"]
        if scans:
            stats.update(ssm_layers=len(scans),
                         ssm_kernel_layers=sum(l.kernel for l in scans))
        deltas = [l for l in self.train_net.layers
                  if l.type == "GatedDeltaNet"]
        if deltas:
            stats.update(gdn_layers=len(deltas),
                         gdn_kernel_layers=sum(l.kernel for l in deltas))
        cores = [l for l in self.train_net.layers
                 if isinstance(l, AttentionLayer)]
        if cores:
            stats.update(attn_core_layers=len(cores), attn_kernel_layers=sum(
                l.kernel == "splash" for l in cores))
        windowed = [l for l in cores
                    if l.type == "GatedAttention" and l.window]
        if windowed:
            visited, causal = (sum(n) for n in
                               zip(*(l.visited for l in windowed)))
            stats.update(
                swa_window_layers=len(windowed),
                swa_band_layers=sum(l.band for l in windowed),
                swa_block_share=100.0 * visited / causal if causal else 0.0)
        loads = {name: np.asarray(st["load"]) for name, st in state.items()
                 if "load" in st}
        if not loads:
            return stats
        first = next(iter(loads.values()))
        stats.update(moe_load_max=int(max(a.max() for a in loads.values())),
                     moe_pairs=int(first.sum()), moe_experts=int(first.size))
        layers = [l for l in self.train_net.layers if l.name in loads]
        if any(l.experts_held < l.num_experts for l in layers):
            held = [int(loads[l.name][l.first_expert:][:l.experts_held].sum())
                    for l in layers]
            moved = [CAPACITY_TILE * live_tiles(n) for n in held]
            stats.update(moe_layers=len(layers), moe_pairs_held=sum(held),
                         moe_rows_moved=sum(moved),
                         moe_compact_layers=sum(
                             rows < stats["moe_pairs"] for rows in moved))
        biases = [np.asarray(st["bias"]) for st in state.values()
                  if "bias" in st]
        if biases:
            stats.update(moe_bias_min=float(min(b.min() for b in biases)),
                         moe_bias_max=float(max(b.max() for b in biases)))
        return stats

    def _step_scanned(self, num_iters: int, data_fn: DataFn, callback,
                      scan_chunk: int) -> float:
        """The scan-fused body of :meth:`step` (see its docstring)."""
        import math

        import numpy as np

        cfg = self.config
        chunk = max(1, min(scan_chunk, num_iters))
        for cadence in (cfg.display,
                        cfg.snapshot if cfg.snapshot_prefix else 0):
            if cadence:
                chunk = math.gcd(chunk, cadence)
        if not hasattr(self, "_scan_fns"):
            self._scan_fns: dict = {}

        done = 0
        while done < num_iters:
            n = min(chunk, num_iters - done)
            if cfg.snapshot and cfg.snapshot_prefix:
                # a resume can start between snapshot boundaries: cap the
                # chunk so every boundary lands exactly at a chunk end
                # (the save must see the boundary-iteration state)
                n = min(n, cfg.snapshot - (self.iter % cfg.snapshot))
            if n < 2:
                # single-step chunk (tail, or one iter shy of a snapshot
                # boundary): the per-iteration path implements every hook
                # exactly; larger chunks may still follow
                self.step(1, data_fn, callback)
                done += 1
                continue
            if n not in self._scan_fns:
                self._scan_fns[n], _, _, _ = self.jitted_scan_steps(
                    n, donate=False, stacked_feeds=True)
            fn = self._scan_fns[n]
            start = self.iter
            # sn.step: the data wait and the dispatch of one scanned chunk
            with step_span("sn.step", start) as sp:
                host = [data_fn(start + i) for i in range(n)]
                if self._obs_in_step:
                    self._obs_images_per_iter = self._feed_images(host[0])
                if any(isinstance(v, jax.Array) for v in host[0].values()):
                    # prefetched feeds are already device-resident: stack
                    # on device — np.asarray here would force a blocking
                    # D2H of every batch, serializing the pipeline
                    # prefetch overlaps
                    stacked = {
                        k: jnp.stack([h[k] for h in host]) for k in host[0]
                    }
                else:
                    stacked = jax.device_put({
                        k: np.stack([np.asarray(h[k]) for h in host])
                        for k in host[0]
                    })
                args = (self.variables, self.slots, start, stacked, self._key)
                self.variables, self.slots, losses = fn(*args)
                account_compiled(sp, self._accounted, fn, *args)
            with Span(None, "sn.step.fence", it=start + n) as sp:
                losses = np.asarray(losses)
                sp.fence_value(losses[-1])
                sp.set(**hbm_live(self._devices))
            # solver state is at the CHUNK END from here on: advance iter
            # BEFORE replaying the per-iteration hooks so a callback that
            # snapshots (the CLI's signal hook) or stops records iter and
            # params from the same point — never iter=k with k+m params
            self.iter = start + n
            for i in range(n):
                loss = float(losses[i])
                self._loss_window.append(loss)
                if len(self._loss_window) > cfg.average_loss:
                    self._loss_window.pop(0)
                it_i = start + i + 1
                if cfg.display and it_i % cfg.display == 0:
                    print(
                        f"Iteration {it_i}, loss = "
                        f"{self._smoothed():.6g}, "
                        f"lr = {float(learning_rate(cfg, it_i)):.6g}"
                    )
                if callback:
                    callback(it_i, loss)
            if (cfg.snapshot and cfg.snapshot_prefix
                    and self.iter % cfg.snapshot == 0):
                self.save(f"{cfg.snapshot_prefix}_iter_{self.iter}")
            done += n
        self.smoothed_loss = self._smoothed()
        return self.smoothed_loss

    # ------------------------------------------------------------------
    def _feed_images(self, feeds) -> int:
        """Images per solver iteration in one feed dict (iter_size > 1
        feeds carry a leading [iter_size] micro-batch axis)."""
        for v in feeds.values():
            shp = getattr(v, "shape", None)
            if shp:
                if self.config.iter_size > 1 and len(shp) > 1:
                    return int(shp[0]) * int(shp[1])
                return int(shp[0])
        return 0

    def _emit_obs_round(self, rec, it0: int, t0: float) -> None:
        """One obs round record for a completed :meth:`step` call.

        The wall is closed on the VALUE of the last loss — either a
        direct ``value_fence`` fetch of the final program's own output
        (the per-iteration path keeps losses as device arrays), or the
        ``np.asarray(losses)`` materialization the scanned path already
        performed.  Threaded state makes the final step depend on every
        predecessor, so one fence covers the whole round."""
        from sparknet_tpu.common import value_fence

        if not self._loss_window:
            return
        loss = self._loss_window[-1]
        if isinstance(loss, jax.Array):
            loss_val = value_fence(loss)
        else:
            loss_val = float(loss)
        from sparknet_tpu.obs import lineage as obs_lineage

        rec.round(
            mode="solo", tau=1, devices=1, iters=self.iter - it0,
            batch=int(self._obs_images_per_iter),
            wall_s=time.perf_counter() - t0, loss=loss_val, fenced=True,
            iteration=self.iter,
            lineage=obs_lineage.round_lineage(
                "solo", it0, it0, max(it0, self.iter - 1)),
        )

    def solve(
        self,
        train_fn: DataFn,
        test_fns=None,
        resume_file: str | None = None,
        callback=None,
    ) -> float:
        """Full optimization run (ref: Solver::Solve solver.cpp:285-326):
        optional restore -> ``Step(max_iter - iter)`` -> snapshot unless
        ``snapshot_after_train`` is off or the last iter already snapshot
        -> final forward-only display pass -> final ``TestAll`` when
        ``max_iter`` lands on a ``test_interval`` boundary.

        In-loop testing during Step stays disabled, matching the
        reference fork's deliberate change (solver.cpp:204-212) — drive
        periodic eval from the app loop instead.  A ``callback`` raising
        ``KeyboardInterrupt`` is the early-exit path (SolverAction.STOP):
        the snapshot still happens, the final display/test passes don't.

        Returns the final display loss (or the smoothed loss when
        ``display`` is off).

        With ``SPARKNET_OBS`` armed the whole run is wrapped in one obs
        span, stamped with the returned loss (a value materialized from
        the final program's own output — :meth:`step` fences by value,
        and the display pass reads ``float(loss_arr)``)."""
        rec = get_recorder()
        if not rec:
            return self._solve_impl(train_fn, test_fns, resume_file,
                                    callback)
        with rec.span("solver.solve") as sp:
            loss = self._solve_impl(train_fn, test_fns, resume_file,
                                    callback)
            sp.fence_value(loss)
        return loss

    def _solve_impl(self, train_fn, test_fns=None, resume_file=None,
                    callback=None) -> float:
        """The body of :meth:`solve` (see its docstring)."""
        cfg = self.config
        early_exit = False
        if resume_file:
            self.restore(resume_file)
        try:
            self.step(max(cfg.max_iter - self.iter, 0), train_fn, callback)
        except KeyboardInterrupt:
            early_exit = True
            self.smoothed_loss = self._smoothed()
        # skip the final save only when Step itself just wrote one (it
        # does so at snapshot boundaries AND only with a prefix set)
        step_just_snapshot = (
            cfg.snapshot
            and cfg.snapshot_prefix
            and self.iter % cfg.snapshot == 0
            and self.iter > 0
        )
        if cfg.snapshot_after_train and not step_just_snapshot:
            prefix = cfg.snapshot_prefix or "solver"
            self.save(f"{prefix}_iter_{self.iter}")
        if early_exit:
            return self.smoothed_loss
        loss = self.smoothed_loss
        if cfg.display and self.iter % cfg.display == 0:
            # forward-only pass to display the post-update loss
            feeds = train_fn(self.iter)
            if cfg.iter_size > 1:
                # train feeds carry a leading [iter_size] micro-batch
                # axis; a single forward takes one micro-batch
                feeds = {k: v[0] for k, v in feeds.items()}
            _, _, loss_arr = self.train_net.apply(
                self.variables, feeds, rng=step_key(self._key, self.iter),
                train=True,
            )
            loss = float(loss_arr)
            print(
                f"Iteration {self.iter}, loss = {loss:.6g}, "
                f"lr = {float(learning_rate(cfg, self.iter)):.6g}"
            )
        if (
            test_fns is not None
            and cfg.test_interval
            and self.iter % cfg.test_interval == 0
        ):
            self.test_all(test_fns)
        return loss

    def _smoothed(self) -> float:
        if not self._loss_window:
            return 0.0
        return float(sum(float(l) for l in self._loss_window) / len(self._loss_window))

    # ------------------------------------------------------------------
    def test(
        self, num_batches: int, data_fn: DataFn, test_net_id: int = 0
    ) -> dict[str, float]:
        """Distributed-eval semantics of the reference: accumulate each test
        output over batches, then divide by batch count (ref:
        Solver::TestAndStoreResult solver.cpp:414-444 + CifarApp.scala:113-115
        average-of-per-batch-scores).  ``test_net_id`` selects among the
        test_state nets (ref: Solver::Test(test_net_id) solver.cpp:329)."""
        step = self._eval_steps[test_net_id]
        sums: dict[str, float] = {}
        for b in range(num_batches):
            outs = step(self.variables, data_fn(b))
            for name, val in outs.items():
                sums[name] = sums.get(name, 0.0) + float(jnp.sum(val))
        return {k: v / num_batches for k, v in sums.items()}

    def test_all(self, data_fns) -> list[dict[str, float]]:
        """Run every test net with its own test_iter count (ref:
        Solver::TestAll solver.cpp:323-327).  ``data_fns``: one DataFn per
        test net."""
        cfg = self.config
        data_fns = list(data_fns)
        if len(data_fns) != len(self.test_nets):
            raise ValueError(
                f"test_all needs one data_fn per test net: got "
                f"{len(data_fns)} for {len(self.test_nets)} nets"
            )
        results = []
        for i, fn in enumerate(data_fns):
            iters = cfg.test_iter[i] if i < len(cfg.test_iter) else 1
            results.append(self.test(iters, fn, test_net_id=i))
        return results

    # ------------------------------------------------------------------
    # Snapshot/restore (ref: Solver::Snapshot/Restore solver.cpp:447-519 +
    # SGDSolver history snapshot sgd_solver.cpp:242+).
    def save(self, prefix: str, format: str = "npz",
             background: bool = False) -> str:
        """``format="npz"``: single-host flat archive. ``format="orbax"``:
        sharded pod-scale checkpoint (each process writes its own shards;
        restores with the live shardings).  ``background=True`` (orbax
        only) streams the write while training continues; the snapshot
        commits at the next save or ``orbax_io.wait_pending()``."""
        if format == "orbax":
            from sparknet_tpu.solvers.orbax_io import save_orbax

            out = save_orbax(self, prefix, background=background)
            if not background:
                # background saves write the orbax state only: the
                # .caffemodel companion gathers every param to host
                # synchronously, which would stall the very step loop
                # the async path exists to protect
                self._export_model_pair(prefix)
            return out
        if background:
            raise ValueError("background saves need format='orbax'")
        if format != "npz":
            raise ValueError(f"unknown snapshot format {format!r} (npz|orbax)")
        path = f"{prefix}.solverstate.npz"
        self._export_model_pair(prefix)
        flat: dict[str, np.ndarray] = {"__iter__": np.asarray(self.iter)}
        # `layout` is provenance, not a compatibility gate: params and
        # state are layout-INVARIANT (conv OIHW, fc wire-order — see
        # ops/layout.py), so a snapshot written under either layout
        # restores exactly into a solver running the other.
        from sparknet_tpu.common import get_config as _gc

        flat["__meta__"] = np.frombuffer(
            json.dumps({"solver_type": self.config.solver_type,
                        "layout": _gc().layout}).encode(), dtype=np.uint8
        )
        for lname, plist in self.variables.params.items():
            for i, p in enumerate(plist):
                flat[f"param/{lname}/{i}"] = np.asarray(p)
        for lname, s in self.variables.state.items():
            for k, v in s.items():
                flat[f"state/{lname}/{k}"] = np.asarray(v)
        for lname, slist in self.slots.items():
            for i, slot in enumerate(slist):
                for j, h in enumerate(slot):
                    flat[f"hist/{lname}/{i}/{j}"] = np.asarray(h)
        # atomic commit: write the archive to a temp file in the SAME
        # directory, then os.replace — a poller (loop/watcher.py) that
        # lists the final name gets a complete archive or nothing,
        # never a torn zip.  np.savez appends ".npz" to suffix-less
        # string paths, so the temp write goes through an open file
        # object to keep the name literal.
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path) or ".",
            prefix=os.path.basename(path) + ".tmp.")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **flat)
            os.replace(tmp, path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise
        return path

    def _export_model_pair(self, prefix: str) -> None:
        """The model file beside the state, like the reference's
        .caffemodel/.solverstate pair (ref: Solver::Snapshot
        solver.cpp:447-466); ``snapshot_format`` picks the wire format."""
        fmt = self.config.snapshot_format.upper()
        if not fmt:
            return
        leaves = [
            p
            for plist in self.variables.params.values()
            for p in plist
            if isinstance(p, jax.Array)
        ]
        if any(not p.is_fully_addressable for p in leaves):
            # pod-scale sharded params: the host-side wire export cannot
            # materialize them here; the orbax checkpoint is the artifact
            print(
                f"skipping {fmt} model export at {prefix!r}: params span "
                "non-addressable devices (use the orbax checkpoint)"
            )
            return
        from sparknet_tpu.net import export_caffemodel, export_hdf5

        if fmt == "BINARYPROTO":
            export_caffemodel(
                self.train_net, self.variables.params,
                f"{prefix}.caffemodel", state=self.variables.state,
            )
        else:  # validated to HDF5 at construction
            export_hdf5(
                self.train_net, self.variables.params,
                f"{prefix}.caffemodel.h5", state=self.variables.state,
            )

    def restore(self, path: str) -> None:
        if path.endswith(".orbax") or os.path.isdir(path):
            from sparknet_tpu.solvers.orbax_io import restore_orbax

            restore_orbax(self, path)
            return
        data = np.load(path)
        meta = json.loads(bytes(data["__meta__"]).decode()) if "__meta__" in data.files else {}
        saved_type = meta.get("solver_type")
        if saved_type and saved_type != self.config.solver_type:
            raise ValueError(
                f"snapshot was taken with solver_type={saved_type!r}, "
                f"this solver is {self.config.solver_type!r}"
            )
        self.iter = int(data["__iter__"])
        params = {k: list(v) for k, v in self.variables.params.items()}
        state = {k: dict(v) for k, v in self.variables.state.items()}
        slots = {k: [list(s) for s in v] for k, v in self.slots.items()}
        for key in data.files:
            parts = key.split("/")
            if parts[0] == "param":
                params[parts[1]][int(parts[2])] = jnp.asarray(data[key])
            elif parts[0] == "state":
                state[parts[1]][parts[2]] = jnp.asarray(data[key])
            elif parts[0] == "hist":
                slots[parts[1]][int(parts[2])][int(parts[3])] = jnp.asarray(data[key])
        self.variables = NetVars(params=params, state=state)
        self.slots = slots
