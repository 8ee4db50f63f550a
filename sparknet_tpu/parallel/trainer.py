"""The distributed training driver.

This file IS the SparkNet algorithm, re-designed for TPU.  The reference's
outer loop (ref: src/main/scala/apps/CifarApp.scala:95-136):

    broadcast(weights); workers.foreach(setWeights)       # driver -> workers
    workers: train(tau)  # tau local SGD steps            # compute
    weights = workers.map(getWeights).reduce(add) / n     # workers -> driver

becomes ONE jitted XLA program per outer iteration: a `shard_map` over the
mesh's data axis in which every device runs `tau` local solver steps
(`lax.scan`) and then `lax.pmean`s the model — the broadcast+collect star
topology through the Spark driver is replaced by an ICI all-reduce, and the
weights never leave HBM (compare the reference's measured JNA float-by-float
weight copy hot spot, ref: src/main/scala/libs/Net.scala:131-171 +
WeightCollectionSpec.scala:20-32).

tau=1 degenerates to fully-synchronous data-parallel SGD and takes an even
simpler path: params replicated, batch sharded over 'data', and GSPMD
inserts the gradient all-reduce inside the fused train step — the TPU analog
of Caffe's own P2PSync tree (ref: caffe/src/caffe/parallel.cpp:202-435).
tau>1 is the paper's communication-reduction knob (tau=10 CIFAR, tau=50
ImageNet — ref: CifarApp.scala:119, ImageNetApp.scala:151).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sparknet_tpu.common import get_config
from sparknet_tpu.compiler.graph import NetVars
from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import Span, feed_counts
from sparknet_tpu.net import WeightCollection, collection_to_variables, variables_to_collection
from sparknet_tpu.parallel.mesh import data_parallel_mesh, shard_map
from sparknet_tpu.parallel.sharding import (
    ShardingRules,
    batch_sharding,
    param_shardings,
    place,
)
from sparknet_tpu.solvers.solver import Solver
from sparknet_tpu.utils.profiling import (account_compiled, hbm_live,
                                          step_span)

DataFn = Callable[[int], dict[str, Any]]


class ParallelTrainer:
    """Distributed trainer over a device mesh.

    tau == 1: synchronous DP (+ optional tensor parallelism via rules).
    tau  > 1: SparkNet periodic model averaging; every `train_round()` runs
    tau local steps per data-shard then averages params+state over the mesh.
    elastic_alpha > 0: EASGD — workers elastically couple to a replicated
    center variable every round instead of hard-averaging (the reference's
    unrealized ROADMAP.md:11 "elastic SGD"; Zhang et al. 2015).  Use
    alpha ≈ 0.9 / num_workers (moving rate β = p·α ≤ 1); eval/get_weights
    expose the center.
    """

    def __init__(
        self,
        solver: Solver,
        mesh=None,
        tau: int = 1,
        rules: ShardingRules | None = None,
        elastic_alpha: float = 0.0,
    ):
        # sn.trainer.build: the mesh, and the variables and slots
        # replicated and placed on it; unfenced, like sn.solver.build
        with Span(None, "sn.trainer.build", host=True,
                  compile_stats=True) as sp:
            cfg = get_config()
            if solver.config.iter_size > 1:
                raise ValueError(
                    "ParallelTrainer does not support iter_size > 1: the feed "
                    "layout [iter_size, B, ...] conflicts with the trainer's "
                    "batch/tau axis contract. Use a larger per-device batch or "
                    "tau-step accumulation instead."
                )
            self.solver = solver
            self.mesh = mesh if mesh is not None else data_parallel_mesh()
            sp.set(devices=int(self.mesh.devices.size))
            self.tau = int(tau)
            self.data_axis = cfg.data_axis
            self.num_workers = self.mesh.shape.get(cfg.data_axis, 1)
            # processes the mesh spans: >1 switches _put_feeds to per-process
            # shard assembly; a process-local sub-mesh stays single-host
            self._mesh_procs = len({d.process_index for d in self.mesh.devices.flat})
            # data-axis width THIS process feeds (the per-host worker count a
            # driver loop should build batches for)
            self.num_local_workers = max(self.num_workers // self._mesh_procs, 1)
            self.iter = 0
            # jitted round -> the entries of its jit cache whose HBM account
            # a span carries (``account_compiled``); the chips whose live
            # bytes the fences read
            self._accounted: dict = {}
            self._devices = self.mesh.local_devices
            # Optional post-placement feed hook (``fn(feeds, it) -> feeds``,
            # e.g. DeviceAugment.trainer_device_fn): applied AFTER _put_feeds
            # and BEFORE the jitted round program, so the uint8 wire's
            # device-resident augment runs on-device without touching the
            # round program itself (banked graph/mem manifests stay
            # byte-identical whether or not the hook is armed).
            self.feed_device_fn = None
            # the round placed ahead (``_place_ahead``): (it, data fn, what
            # staging it gave or raised), or None
            self._ahead = None
            self._step_fn = solver._make_train_step(debug=False)
            self._rules = rules or ShardingRules()
            self._pshard = param_shardings(
                solver.train_net, solver.variables, self.mesh, self._rules
            )

            # Sequence parallelism: a 'seq' mesh axis + rules.sequence_parallel
            # shards feed axis 1 over it and routes MultiHeadAttention layers
            # through ring/Ulysses at trace time (ops.attention context).
            from sparknet_tpu.parallel.mesh import mesh_seq_size

            self._seq_size = (
                mesh_seq_size(self.mesh) if self._rules.sequence_parallel else 1
            )
            if self._seq_size > 1 and (self.tau > 1 or elastic_alpha > 0):
                raise ValueError(
                    "sequence parallelism (a 'seq' mesh axis) composes with "
                    "tau=1 synchronous DP only: the tau>1/EASGD rounds are "
                    "already a manual shard_map over 'data' and cannot nest "
                    "the seq-axis attention shard_map. Use tau=1, or a mesh "
                    "without a 'seq' axis."
                )

            self.elastic_alpha = float(elastic_alpha)
            self._elastic = elastic_alpha > 0.0
            if elastic_alpha and not (
                0.0 < elastic_alpha * self.num_workers <= 1.0
            ):
                # EASGD stability: the center's moving rate is beta = p*alpha
                # and must stay in (0, 1] (Zhang et al. 2015 use beta = 0.9)
                raise ValueError(
                    f"elastic_alpha={elastic_alpha} violates the stability "
                    f"bound alpha*num_workers <= 1 with "
                    f"{self.num_workers} workers; use ~0.9/{self.num_workers}"
                )

            if self.tau == 1 and not self._elastic:
                self.variables = place(solver.variables, self._pshard)
                self.slots = self._place_slots(solver.slots)
                # Pin the carry's OUTPUT shardings to its input shardings:
                # with TP/SP axes live, GSPMD otherwise propagates activation
                # shardings into updated params (graphcheck caught ip-style
                # weights returning P(None,'model') after entering P()), so
                # every round paid an entry reshard and the changed layout
                # broke the donation aliasing for those leaves.
                out_shards = (
                    self._pshard,
                    {
                        lname: [
                            [self._pshard.params[lname][i]] * len(hl)
                            for i, hl in enumerate(per_param)
                        ]
                        for lname, per_param in solver.slots.items()
                    },
                    NamedSharding(self.mesh, P()),  # scalar loss
                )
                self._train = jax.jit(self._step_fn, donate_argnums=(0, 1),
                                      out_shardings=out_shards)
            else:
                # stack a worker axis: leaf [R, ...] sharded over 'data' — each
                # device owns its own (initially identical) model replica
                self.variables = self._stack_replicas(solver.variables)
                self.slots = self._stack_replicas(solver.slots)
                if self._elastic:
                    # EASGD (Zhang, Choromanska, LeCun 2015 — the reference's
                    # unrealized ROADMAP.md:11 item): workers couple to a
                    # replicated CENTER variable instead of hard-averaging
                    rep = NamedSharding(self.mesh, P())
                    self.center = jax.tree_util.tree_map(
                        lambda x: jax.device_put(x, rep), solver.variables.params
                    )
                    self._train = jax.jit(
                        self._make_elastic_round(), donate_argnums=(0, 1, 2)
                    )
                else:
                    self._train = jax.jit(
                        self._make_tau_round(), donate_argnums=(0, 1)
                    )

            # tau>1 keeps per-replica params; average once per test() call (not
            # per batch) and feed the solver's own jitted eval step — one shared
            # implementation of the TestAndStoreResult semantics.
            self._average = jax.jit(
                lambda v: jax.tree_util.tree_map(lambda x: x.mean(0), v)
            )

    # ------------------------------------------------------------------
    def _stack_replicas(self, tree):
        """Leaf ``x`` -> ``[R, ...]`` sharded over 'data', every device
        handed its own ``[1, ...]`` row straight from the host copy —
        the R-fold stack never exists on any one device."""
        R = self.num_workers
        spec = NamedSharding(self.mesh, P(self.data_axis))

        def one(x):
            row = np.asarray(x)[None]
            return jax.make_array_from_callback(
                (R,) + row.shape[1:], spec, lambda _idx: row)

        return jax.tree_util.tree_map(one, tree)

    def _place_slots(self, slots):
        """Slots shard exactly like the param they track."""
        out = {}
        for lname, per_param in slots.items():
            shards = self._pshard.params[lname]
            out[lname] = [
                [jax.device_put(h, shards[i]) for h in hl]
                for i, hl in enumerate(per_param)
            ]
        return out

    # ------------------------------------------------------------------
    def _local_tau_steps(self, v_blk, s_blk, it_, feeds_blk, key_):
        """Per-worker leg shared by both stacked rounds: unstack this
        worker's replica, run tau local solver steps over the feed slots."""
        step, axis = self._step_fn, self.data_axis
        sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        v, sl = sq(v_blk), sq(s_blk)
        wkey = jax.random.fold_in(key_, jax.lax.axis_index(axis))

        def one(carry, feed):
            v, sl, i = carry
            v, sl, loss = step(v, sl, i, feed, wkey)
            return (v, sl, i + 1), loss

        (v, sl, _), losses = jax.lax.scan(one, (v, sl, it_), feeds_blk)
        return v, sl, jax.lax.pmean(jnp.mean(losses), axis)

    def _make_tau_round(self):
        axis = self.data_axis
        in_specs = (P(axis), P(axis), P(), P(None, axis), P())
        out_specs = (P(axis), P(axis), P())
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)

        def round_fn(variables, slots, it, feeds, key):
            def body(v_blk, s_blk, it_, feeds_blk, key_):
                v, sl, loss = self._local_tau_steps(
                    v_blk, s_blk, it_, feeds_blk, key_
                )
                # THE sync: collect+average over workers == pmean over ICI
                # (ref: CifarApp.scala:132-134 reduce(add)/scalarDivide)
                v = jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis), v)
                return ex(v), ex(sl), loss

            return shard_map(
                body,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
            )(variables, slots, it, feeds, key)

        return round_fn

    # ------------------------------------------------------------------
    def _make_elastic_round(self):
        """EASGD round: tau local steps per worker, then the elastic
        update  x_i -= α(x_i - x̃);  x̃ += α·Σ_i(x_i - x̃)  (moving rate
        β = p·α).  Workers stay DISTINCT replicas — exploration — while
        the center integrates them; β = p·α ≤ 1 for stability (choose
        α ≈ 0.9/p).  BatchNorm-style state is hard-averaged."""
        axis = self.data_axis
        alpha = self.elastic_alpha
        in_specs = (P(axis), P(axis), P(), P(), P(None, axis), P())
        out_specs = (P(axis), P(axis), P(), P())
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)

        def round_fn(variables, slots, center, it, feeds, key):
            def body(v_blk, s_blk, center_, it_, feeds_blk, key_):
                v, sl, loss = self._local_tau_steps(
                    v_blk, s_blk, it_, feeds_blk, key_
                )
                diff = jax.tree_util.tree_map(
                    lambda x, c: x - c, v.params, center_
                )
                new_params = jax.tree_util.tree_map(
                    lambda x, d: x - alpha * d, v.params, diff
                )
                new_center = jax.tree_util.tree_map(
                    lambda c, d: c + alpha * jax.lax.psum(d, axis), center_, diff
                )
                new_state = jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(x, axis), v.state
                )
                v = NetVars(params=new_params, state=new_state)
                return ex(v), ex(sl), new_center, loss

            return shard_map(
                body,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
            )(variables, slots, center, it, feeds, key)

        return round_fn

    # ------------------------------------------------------------------
    def _put_feeds(self, feeds, with_tau_axis: bool):
        """Batch axis -> 'data' axis.  tau-mode arrays are [tau, B, ...]
        and shard axis 1.

        Single process: the whole global batch is addressable and one
        device_put scatters it.  Multi-host (``jax.process_count() > 1``,
        DCN bring-up via ``initialize_distributed``): each process feeds
        only its own shard — the per-worker stream shape of the reference
        (each Spark executor reads its partition, ref:
        CifarApp.scala:118-130) — and the global array is assembled
        process-locally without any cross-host data motion."""
        def spec_for(name, v):
            if with_tau_axis:
                return NamedSharding(self.mesh, P(None, self.data_axis))
            if self._seq_size > 1 and np.ndim(v) >= 2:
                # sequence models: feed axis 1 is the sequence dimension
                # ([B, S] ids / [B, S, E] embeddings / [B, S] labels) and
                # shards over 'seq' alongside the batch over 'data'.
                # rules.seq_feeds selects feeds explicitly; the default
                # (None) applies to any feed whose axis 1 divides evenly,
                # falling back to batch-only sharding otherwise (sharding
                # is layout, not semantics — GSPMD reshards inside the
                # program, and the attention shard_map forces its own
                # specs — so a skipped/extra feed costs transfer, never
                # correctness).
                listed = self._rules.seq_feeds
                divisible = np.shape(v)[1] % self._seq_size == 0
                if listed is not None and name in listed:
                    if not divisible:
                        raise ValueError(
                            f"feed {name!r}: sequence length "
                            f"{np.shape(v)[1]} not divisible by the "
                            f"'seq' mesh axis ({self._seq_size})"
                        )
                    wanted = True
                else:
                    wanted = listed is None and divisible
                if wanted:
                    return NamedSharding(
                        self.mesh, P(self.data_axis, get_config().seq_axis)
                    )
            return batch_sharding(self.mesh)

        mesh_procs = self._mesh_procs
        if mesh_procs > 1:
            out = {}
            bax = 1 if with_tau_axis else 0
            for k, v in feeds.items():
                v = np.asarray(v)
                gshape = (
                    v.shape[:bax]
                    + (v.shape[bax] * mesh_procs,)
                    + v.shape[bax + 1:]
                )
                out[k] = jax.make_array_from_process_local_data(
                    spec_for(k, v), v, gshape
                )
            return out
        # host batches go straight to their shards: staging the global
        # batch on the default device first would put every worker's
        # share on chip 0
        return {
            k: jax.device_put(
                v if isinstance(v, jax.Array) else np.asarray(v),
                spec_for(k, v))
            for k, v in feeds.items()
        }

    # ------------------------------------------------------------------
    def train_round(self, data_fn: DataFn) -> float:
        """One outer iteration.

        tau == 1: data_fn(it) -> feeds [B_global, ...]; one sync-SGD step.
        tau  > 1: data_fn(it) -> feeds [tau, B_global, ...]; tau local steps
        on every worker, then model averaging.  elastic_alpha > 0 always
        takes the tau-shaped feed contract ([tau, B_global, ...], tau may
        be 1) and applies the EASGD elastic update instead of averaging.
        On a multi-process mesh the batch axis is the PER-PROCESS shard
        instead of B_global — each host feeds only its own partition (see
        _put_feeds).  Returns mean loss (device value materialized — call
        sites that care about overlap should batch rounds).

        The tau-shaped contract is placed ONE round ahead: between this
        round's dispatch and its fence the next round is asked of
        ``data_fn``, placed and augmented (``_place_ahead``), so pass the
        same ``data_fn`` every round (a new function a round has every
        round staged twice, ``_take_ahead``), let it leave a call's arrays
        alone until the call after the next returns, and ``close()`` the
        trainer after the last round.

        With ``SPARKNET_OBS`` armed each round emits one obs record
        (wall fence-stamped on the loss VALUE, comm_model-predicted
        collective bytes attached); disabled, the body is untouched —
        the fenced return value IS the ``float(loss)`` this method
        always materialized, so obs adds zero extra dispatches either
        way."""
        rec = get_recorder()
        t0 = time.perf_counter() if rec else 0.0
        it0 = self.iter
        stacked = self._elastic or self.tau > 1
        # dispatch -> data, put, augment of the NEXT round -> fence, each
        # stage with its own wall on the profiler's clock; a round nothing
        # was placed ahead for begins with its own data, put, augment
        with step_span("sn.round", it0) as round_sp:
            batch, feeds = (
                self._take_ahead(data_fn, it0)
                or self._stage_round(data_fn, it0, stacked, staged=0))
            args = (self.variables, self.slots,
                    *((self.center,) if self._elastic else ()),
                    it0, feeds, self.solver._key)
            with rec.span("sn.round.dispatch", host=True, it=it0), \
                    self._sp_context():
                out = self._train(*args)
                account_compiled(round_sp, self._accounted, self._train, *args)
            if self._elastic:
                self.variables, self.slots, self.center, loss = out
            else:
                self.variables, self.slots, loss = out
            self.iter += self.tau
            if stacked:
                self._place_ahead(data_fn)
            # on the profiler's clock only (no Recorder): in the journal
            # the round record closed on this value is the fence's line
            with Span(None, "sn.round.fence", it=it0) as sp:
                if rec:
                    loss_val = self._emit_obs_round(rec, batch, t0, loss)
                else:
                    loss_val = float(loss)
                sp.fence_value(loss_val)
                sp.set(**hbm_live(self._devices))
        return loss_val

    def _stage_round(self, data_fn: DataFn, it: int, stacked: bool,
                     staged: int):
        """Round ``it``'s feeds from the data fn to the device: (images a
        local step, placed feeds).  ``staged`` = 1 on ``sn.round.data``
        when this is done before the round before it is fenced."""
        with get_recorder().span("sn.round.data", host=True, it=it,
                                 staged=staged):
            raw = data_fn(it)
        batch = 0
        for v in raw.values():
            shp = np.shape(v)
            if shp:
                batch = int(shp[1]) if stacked and len(shp) > 1 \
                    else int(shp[0])
                break
        return batch, self._stage_feeds(raw, it, with_tau_axis=stacked)

    def _place_ahead(self, data_fn: DataFn) -> None:
        """The tau-shaped contract's look-ahead, ONE round deep: between
        round n's dispatch and its fence, round n+1 is asked of the data
        fn, placed and handed to the device hook under its OWN ``it``, so
        its transfer and its augment run beside round n's steps and every
        random draw is the serial order's.  An error of the early call
        belongs to round n+1 and is raised by the ``train_round`` that
        would have trained it.  The data fn must leave the arrays of a
        call alone until the call after the next returns (``rounds.
        stack_tau`` does: the host-buffer rule, ``data/rounds.py``).
        tau == 1 (``widen_batch`` / ``train_rounds``) has another rule
        and no look-ahead."""
        it = self.iter
        try:
            got = self._stage_round(data_fn, it, True, staged=1)
        except (Exception, SystemExit) as e:
            got = e
        self._ahead = (it, data_fn, got)

    def _take_ahead(self, data_fn: DataFn, it: int):
        """What ``_place_ahead`` staged, if it was staged for ``it`` and
        asked of ``data_fn``.  Any other round is dropped, its batches
        with it: its augment was keyed by another ``it``, or its records
        are another feed's (a caller that hands ``train_round`` a new
        function every round gets the serial order, and pays for a round
        placed in vain)."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return None
        placed_for, asked_of, got = ahead
        if placed_for != it or asked_of != data_fn:
            return None
        if isinstance(got, BaseException):
            raise got
        return got

    def close(self) -> None:
        """Lets go of the round placed ahead, its shards with it: call it
        when the last round is trained.  The trainer can train on (its
        next round stages its own feeds)."""
        self._ahead = None

    def _stage_feeds(self, raw, it: int, with_tau_axis: bool):
        """Host feeds -> their shards (``_put_feeds``), then the
        post-placement device hook, for the round that begins at ``it``.
        ``sn.feed.put`` / ``sn.feed.augment`` time the HOST side: both
        are dispatched, not awaited.  The placed wire is let go as soon
        as the hook has what it made of it."""
        rec = get_recorder()
        counts = feed_counts(raw, 2 if with_tau_axis else 1)
        with rec.span("sn.feed.put", host=True, it=it, **counts):
            feeds = self._put_feeds(raw, with_tau_axis=with_tau_axis)
        if self.feed_device_fn is not None:
            with rec.span("sn.feed.augment", host=True, compile_stats=True,
                          it=it, images=counts["images"]):
                feeds = self.feed_device_fn(feeds, it)
        return feeds

    def train(self, num_outer: int, data_fn: DataFn, callback=None) -> float:
        loss = 0.0
        for _ in range(num_outer):
            loss = self.train_round(data_fn)
            if callback:
                callback(self.iter, loss)
        return loss

    # ------------------------------------------------------------------
    def _obs_mode(self) -> str:
        """The comm_model mode name this trainer's rounds run as."""
        if self._elastic:
            return "easgd"
        return "tau" if self.tau > 1 else "dp"

    def _obs_comm(self) -> dict | None:
        """comm_model's analytic per-round collective budget for this
        trainer's mode and ACTUAL model sizes — attached to every obs
        round record so a measured wall carries its predicted wire
        volume inline (the runtime tie-in to graphcheck's static
        manifests).  Cached: the model does not change between rounds."""
        cached = getattr(self, "_obs_comm_cache", False)
        if cached is not False:
            return cached
        from sparknet_tpu.analysis.comm_model import expected_comm

        def tree_bytes(tree) -> int:
            return sum(
                int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(tree)
                if hasattr(l, "shape") and hasattr(l, "dtype"))

        # single-replica sizes from the wrapped Solver's tree: tau/EASGD
        # stack a worker axis, but the sync still moves one model's
        # bytes per chip per round (same convention as parallel/modes.py)
        pb = tree_bytes(self.solver.variables.params)
        sb = tree_bytes(self.solver.variables.state)
        try:
            exp = expected_comm(self._obs_mode(), param_bytes=pb,
                                state_bytes=sb)
            comm: dict | None = {
                "param_bytes": pb,
                "state_bytes": sb,
                "predicted": {k: (list(v) if v is not None else None)
                              for k, v in exp.required.items()},
                "note": exp.note,
            }
        except KeyError:
            comm = None
        self._obs_comm_cache = comm
        return comm

    def _emit_obs_round(self, rec, batch: int, t0: float, loss) -> float:
        """Journal one round record; returns the fenced loss VALUE —
        the same number ``float(loss)`` yields (``value_fence`` on the
        scalar loss IS the value fetch), so obs-on and obs-off return
        identically and no extra dispatch is added."""
        from sparknet_tpu.common import value_fence

        loss_val = value_fence(loss)
        wall = time.perf_counter() - t0
        stacked = self.tau > 1 or self._elastic
        from sparknet_tpu.obs import lineage as obs_lineage

        it_consumed = self.tau if stacked else 1
        rec.round(
            mode=self._obs_mode(), tau=self.tau,
            devices=int(self.mesh.devices.size),
            workers=self.num_workers,
            iters=it_consumed, batch=batch,
            wall_s=wall, loss=loss_val, fenced=True,
            comm=self._obs_comm(), iteration=self.iter,
            lineage=obs_lineage.round_lineage(
                self._obs_mode(), self.iter - it_consumed,
                self.iter - it_consumed, self.iter - 1),
        )
        return loss_val

    # ------------------------------------------------------------------
    def train_rounds(self, n: int, data_fn: DataFn) -> float:
        """``n`` tau=1 sync-SGD rounds fused into ONE device dispatch
        (lax.scan over staged global batches; GSPMD still inserts the
        per-step gradient all-reduce inside the loop body).  The scan
        twin of :meth:`Solver.jitted_scan_steps` for the mesh path:
        ``train_round``'s own docstring says call sites that care about
        overlap should batch rounds — this is that batching.  tau>1 and
        EASGD already amortize dispatch over their tau local steps, so
        they (and n<=1) fall back to the per-round loop.  Returns the
        LAST round's global mean loss, like a train_round loop would."""
        if n <= 1 or self.tau != 1 or self._elastic:
            loss = 0.0
            for _ in range(max(n, 1)):
                loss = self.train_round(data_fn)
            return loss
        if not hasattr(self, "_round_scan_fns"):
            self._round_scan_fns: dict = {}
        if n not in self._round_scan_fns:
            # one scan-body implementation lives in the Solver; scan the
            # SAME step function the per-round jit wraps
            self._round_scan_fns[n], _, _, _ = self.solver.jitted_scan_steps(
                n, donate=True, stacked_feeds=True, step_fn=self._step_fn
            )
        rec = get_recorder()
        t0 = time.perf_counter() if rec else 0.0
        it0, fn = self.iter, self._round_scan_fns[n]
        # sn.round: the data, the placement and the dispatch of the n
        # fused rounds, then their one fence
        with step_span("sn.round", it0) as sp:
            host = [data_fn(it0 + i) for i in range(n)]
            stacked = {
                k: np.stack([np.asarray(h[k]) for h in host]) for k in host[0]
            }
            # [n, B, ...]: the tau-shaped feed placement shards axis 1 over
            # 'data' and leaves the round axis unsharded — exactly the scan
            # xs layout
            # (the device hook's rank-5 arm: [n, B, ...] scanned rounds take
            # per-slot keys exactly like a [tau, B, ...] round)
            feeds = self._stage_feeds(stacked, it0, with_tau_axis=True)
            args = (self.variables, self.slots, it0, feeds, self.solver._key)
            with self._sp_context():
                self.variables, self.slots, losses = fn(*args)
                account_compiled(sp, self._accounted, fn, *args)
        self.iter += n
        with Span(None, "sn.round.fence", it=it0) as sp:
            if rec:
                # one obs record for the fused n-round dispatch; value_fence
                # on the [n] loss vector fetches its LAST element — the same
                # number the plain return materializes
                from sparknet_tpu.common import value_fence

                loss_val = value_fence(losses)
                batch = next(
                    (int(np.shape(v)[0]) for v in host[0].values()
                     if np.shape(v)), 0)
                from sparknet_tpu.obs import lineage as obs_lineage

                rec.round(
                    mode="dp", tau=1, devices=int(self.mesh.devices.size),
                    workers=self.num_workers, iters=n, batch=batch,
                    wall_s=time.perf_counter() - t0, loss=loss_val,
                    fenced=True, comm=self._obs_comm(), iteration=self.iter,
                    lineage=obs_lineage.round_lineage(
                        "dp", self.iter - n, self.iter - n, self.iter - 1),
                )
            else:
                loss_val = float(losses[-1])
            sp.fence_value(loss_val)
            sp.set(**hbm_live(self._devices))
        return loss_val

    # ------------------------------------------------------------------
    def _sp_context(self):
        """Trace-time sequence-parallel routing for jitted steps (no-op
        without a 'seq' mesh axis)."""
        if self._seq_size > 1:
            from sparknet_tpu.ops.attention import sequence_parallel

            return sequence_parallel(self.mesh, self._rules.attention_impl)
        import contextlib

        return contextlib.nullcontext()

    # ------------------------------------------------------------------
    def test(self, num_batches: int, data_fn: DataFn) -> dict[str, float]:
        """Distributed eval with the reference's sum-then-normalize semantics
        (ref: Solver::TestAndStoreResult solver.cpp:414-444 +
        CifarApp.scala:113-115)."""
        variables = self._averaged_variables()
        sums: dict[str, float] = {}
        for b in range(num_batches):
            feeds = self._put_feeds(data_fn(b), with_tau_axis=False)
            with self._sp_context():
                outs = self.solver._eval_step(variables, feeds)
            for name, val in outs.items():
                sums[name] = sums.get(name, 0.0) + float(jnp.sum(val))
        return {k: v / num_batches for k, v in sums.items()}

    # ------------------------------------------------------------------
    def _averaged_variables(self) -> NetVars:
        if self._elastic:
            # EASGD evaluates the CENTER variable (consensus model);
            # worker-local BN-style state is averaged (params skipped —
            # the center already is the consensus)
            state = self._average(self.variables.state)
            return NetVars(params=self.center, state=state)
        if self.tau == 1:
            return self.variables
        return self._average(self.variables)

    def get_weights(self) -> WeightCollection:
        """Driver-visible averaged model (ref: Net.scala getWeights)."""
        return variables_to_collection(self._averaged_variables())

    def set_weights(self, wc: WeightCollection) -> None:
        self._ahead = None  # a new model begins with a round of its own
        v = collection_to_variables(wc, self.solver.variables)
        if self.tau == 1 and not self._elastic:
            self.variables = place(v, self._pshard)
        else:
            self.variables = self._stack_replicas(v)
            if self._elastic:
                rep = NamedSharding(self.mesh, P())
                self.center = jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, rep), v.params
                )

    def save(self, prefix: str) -> str:
        """Pod-scale checkpoint of the LIVE distributed state (sharded
        replicas + slots (+ EASGD center) + iteration): each process
        writes only its own shards via orbax — no host gather, unlike
        ``sync_to_solver`` + ``Solver.save``."""
        from sparknet_tpu.solvers.orbax_io import save_trainer_orbax

        return save_trainer_orbax(self, prefix)

    def restore(self, path: str) -> None:
        """Restore a :meth:`save` checkpoint with the live shardings."""
        from sparknet_tpu.solvers.orbax_io import restore_trainer_orbax

        restore_trainer_orbax(self, path)
        self._ahead = None  # placed for the iteration before the restore

    def sync_to_solver(self) -> None:
        """Pull the averaged model AND optimizer history back into the
        wrapped Solver so its snapshot/restore path (ref: solver.cpp:447-519
        + sgd_solver.cpp:242+ history snapshot) sees current state.  tau>1
        slots are per-worker; they are averaged like the reference's driver
        would average any state it chose to persist."""
        self.solver.variables = jax.tree_util.tree_map(
            np.asarray, self._averaged_variables()
        )
        stacked = self.tau > 1 or self._elastic
        slots = self._average(self.slots) if stacked else self.slots
        self.solver.slots = jax.tree_util.tree_map(np.asarray, slots)
        self.solver.iter = self.iter
