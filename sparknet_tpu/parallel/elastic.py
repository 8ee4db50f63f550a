"""Elastic τ-averaging: survive worker loss, joins, and stragglers.

SparkNet's selling point is that periodic model averaging tolerates slow
and flaky workers (Moritz et al., ICLR 2016 — the paper's argument
against synchronous SGD), but the rebuild's ``ParallelTrainer`` only
ever runs a FIXED mesh: the Spark-RDD fault-tolerance layer the
reference leaned on (ref: CifarApp.scala:27-33 executor re-formation;
WorkerStore.scala:5-25 pinned workers) was design-replaced and never
re-demonstrated.  This module is that demonstration in the stronger,
modern form: a trainer whose worker set can grow, shrink, or die
*between averaging rounds* — the production failure mode of preemptible
capacity.

Design (all membership changes happen at ROUND BOUNDARIES — inside a
round the mesh is fixed and the jitted program is the plain tau round):

* **Mesh re-formation** — one jitted weighted-averaging round program
  per worker-set width, cached (``mesh.sized_data_mesh`` re-cuts the
  same device pool); a resize re-places the surviving replicas on the
  new mesh through the blob-wise host path (the same numpy trees the
  checkpoint format stores).
* **Deterministic shard reassignment** — the data contract is
  ``data_fn(g)``: one per-worker batch per GLOBAL shard id ``g``.  A
  round at width W consumes the next ``tau * W`` consecutive ids from
  the epoch cursor and worker ``w`` owns exactly those with
  ``g % W == w`` (:func:`round_shards`), so after any resize no example
  is dropped or double-counted within an epoch — ownership is a pure
  function of (cursor, tau, W), never of scheduling.
* **Optimizer-state-carrying handoff** — a departing worker's
  params+slots fold into the boundary consensus (params are already the
  round average; its slot history joins the slot consensus a joining
  worker adopts), via the blob-wise checkpoint representation.
  Survivors keep their own slots untouched — which is what makes
  kill-at-a-round-boundary equal a run that never had that worker.
* **Bounded-staleness rejoin (async EASGD flavor)** — a straggler
  parked for ``s`` rounds rejoins with its contribution to the round
  average damped to ``staleness_decay ** s`` (fresh workers weigh 1.0;
  the weighted psum replaces the hard pmean), never silently averaged
  as fresh; ``s = 0`` reduces exactly to plain τ-averaging.  A worker
  staler than ``staleness_bound`` rounds is dropped instead (journaled
  ``worker_lost``), so no contribution older than the bound ever
  enters the average.

Verification is chip-free: :class:`FaultPlan` injects kill / join /
delay events into the virtual CPU mesh (tests/test_elastic.py, dryrun
mode 17), the loss-trajectory-equivalence gates pin the membership
semantics, and graphcheck/memcheck bank width-parameterized twin
manifests (``elastic_w{8,6,4}``) so the comm/HBM contracts hold across
re-formation.  Obsnet journals every membership change
(``worker_lost`` / ``worker_joined`` / ``mesh_resize`` — obs/schema.py).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sparknet_tpu.common import get_config
from sparknet_tpu.compiler.graph import NetVars
from sparknet_tpu.net import WeightCollection, variables_to_collection
from sparknet_tpu.obs import get_recorder
from sparknet_tpu.parallel.mesh import shard_map, sized_data_mesh
from sparknet_tpu.solvers.solver import Solver

__all__ = [
    "FaultEvent",
    "FaultPlan",
    "ElasticTrainer",
    "kill",
    "join",
    "delay",
    "round_shards",
]

# A shard-id data function: ``data_fn(g)`` returns ONE per-worker batch
# for global shard id ``g`` (pure function of g — that is what makes a
# dead worker's shards re-ownable without coordination).
ShardFn = Callable[[int], dict[str, Any]]


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled membership change, applied at the BOUNDARY before
    round ``round`` runs.  ``worker`` is the stable worker id (the pool
    renumbers positions on every resize; ids never recycle)."""

    round: int
    kind: str  # "kill" | "join" | "delay"
    worker: int = -1  # kill/delay target (stable id)
    count: int = 1  # join: how many workers arrive
    steps: int = 0  # delay: local steps the straggler falls behind


def kill(worker: int, at_round: int) -> FaultEvent:
    """Worker ``worker`` dies at the boundary before round ``at_round``."""
    return FaultEvent(round=at_round, kind="kill", worker=worker)


def join(at_round: int, count: int = 1) -> FaultEvent:
    """``count`` fresh workers join before round ``at_round`` (adopting
    the consensus params + slot history)."""
    return FaultEvent(round=at_round, kind="join", count=count)


def delay(worker: int, at_round: int, steps: int) -> FaultEvent:
    """Worker ``worker`` straggles by ``steps`` local steps starting at
    the boundary before round ``at_round``: it misses
    ``ceil(steps / tau)`` full rounds, then rejoins staleness-damped."""
    return FaultEvent(round=at_round, kind="delay", worker=worker,
                      steps=steps)


class FaultPlan:
    """A deterministic schedule of membership faults — the test-side
    twin of preemptible capacity (workers die mid-run, capacity comes
    back later).  Drives :class:`ElasticTrainer` in tests and
    ``dryrun_multichip`` mode 17 with zero chip time."""

    def __init__(self, events: tuple[FaultEvent, ...] | list = ()):
        self.events = tuple(sorted(events, key=lambda e: e.round))
        for e in self.events:
            if e.kind not in ("kill", "join", "delay"):
                raise ValueError(f"unknown fault kind {e.kind!r}")
            if e.kind == "delay" and e.steps <= 0:
                raise ValueError("delay events need steps > 0")
            if e.kind == "join" and e.count <= 0:
                raise ValueError("join events need count > 0")

    def at(self, rnd: int) -> list[FaultEvent]:
        return [e for e in self.events if e.round == rnd]


# ---------------------------------------------------------------------------
# Deterministic shard reassignment
# ---------------------------------------------------------------------------


def round_shards(cursor: int, tau: int, width: int) -> np.ndarray:
    """Global shard ids one round consumes, as ``[tau, width]`` — column
    ``w`` holds, in order, the ids with ``g % width == w``.

    The round takes the next ``tau * width`` CONSECUTIVE ids from the
    epoch cursor; because the block length is a multiple of ``width``,
    every worker owns exactly ``tau`` of them under the modulo rule
    regardless of the cursor's alignment — so a resize mid-epoch
    redistributes ownership without dropping or double-counting a
    single shard (the cursor just keeps advancing by ``tau * width'``).
    """
    if width < 1 or tau < 1:
        raise ValueError(f"need tau >= 1 and width >= 1 "
                         f"(got tau={tau}, width={width})")
    ids = np.arange(cursor, cursor + tau * width, dtype=np.int64)
    cols = [ids[ids % width == w] for w in range(width)]
    return np.stack(cols, axis=1)  # [tau, width]


# ---------------------------------------------------------------------------
# Host-side (blob-wise) tree helpers — the checkpoint representation
# ---------------------------------------------------------------------------


def _tree_row(tree, i: int):
    return jax.tree_util.tree_map(lambda x: np.asarray(x[i]), tree)


def _tree_stack(rows: list):
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *rows)


def _tree_mean(rows: list, weights: list[float] | None = None):
    if weights is None:
        return jax.tree_util.tree_map(
            lambda *xs: np.mean(np.stack(xs), axis=0,
                                dtype=np.result_type(xs[0], np.float32)
                                ).astype(xs[0].dtype), *rows)
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree_util.tree_map(
        lambda *xs: np.tensordot(
            w, np.stack(xs).astype(np.float64), axes=1
        ).astype(xs[0].dtype), *rows)


# ---------------------------------------------------------------------------
# The trainer
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Parked:
    """A straggler's retained state while it misses rounds."""

    wid: int
    variables: Any  # blob-wise numpy NetVars (single replica)
    slots: Any
    parked_round: int
    rejoin_round: int


class ElasticTrainer:
    """The τ-averaging round loop over a worker set that can change
    between rounds (see module docstring for the full design).

    ``solver``'s net carries the PER-WORKER batch (the tau-mode shape);
    ``data_fn`` follows the shard-id contract (:data:`ShardFn`).  Off
    the elastic path nothing changes: :class:`ParallelTrainer` and its
    banked manifests are untouched — this class is opt-in and additive.
    """

    def __init__(self, solver: Solver, *, width: int | None = None,
                 tau: int = 1, staleness_decay: float = 0.5,
                 staleness_bound: int = 3, devices=None,
                 plan: FaultPlan | None = None):
        if solver.config.iter_size > 1:
            raise ValueError(
                "ElasticTrainer does not support iter_size > 1 (same "
                "feed-layout conflict as ParallelTrainer)")
        if not (0.0 < staleness_decay <= 1.0):
            raise ValueError(
                f"staleness_decay must be in (0, 1] (got "
                f"{staleness_decay}); decay**s is the rejoin weight")
        self.solver = solver
        self.tau = int(tau)
        self.staleness_decay = float(staleness_decay)
        self.staleness_bound = int(staleness_bound)
        self.plan = plan or FaultPlan()
        self._axis = get_config().data_axis
        self._devices = list(devices) if devices is not None \
            else jax.devices()
        self.width = int(width) if width is not None else len(self._devices)
        if not (1 <= self.width <= len(self._devices)):
            raise ValueError(
                f"width {self.width} needs 1..{len(self._devices)} "
                "devices in the pool")
        self._step_fn = solver._make_train_step(debug=False)
        # one (mesh, jitted round) per width the run has visited —
        # re-formation back to a seen width never recompiles
        self._programs: dict[int, tuple] = {}
        self.mesh = self._mesh_for(self.width)

        # stable worker ids: positions renumber on resize, ids never
        # recycle (journal events name ids, not positions)
        self._wids = list(range(self.width))
        self._next_wid = self.width
        self._parked: list[_Parked] = []
        self._round_weights = np.ones((self.width,), np.float32)

        # stacked replica state [W, ...] sharded over 'data' — every
        # worker starts from the same solver init (the broadcast step of
        # the reference's outer loop, ref: CifarApp.scala:95-136)
        rows_v = [jax.tree_util.tree_map(np.asarray, solver.variables)
                  ] * self.width
        rows_s = [jax.tree_util.tree_map(np.asarray, solver.slots)
                  ] * self.width
        self.variables = self._place(_tree_stack(rows_v), self.mesh)
        self.slots = self._place(_tree_stack(rows_s), self.mesh)

        self.iter = 0  # solver iterations (advances by tau per round)
        self.round = 0  # averaging rounds completed
        self.cursor = 0  # global shard ids consumed
        # Optional post-placement feed hook (``fn(feeds, it) -> feeds``,
        # DeviceAugment.trainer_device_fn): runs after _place_feeds and
        # before the width-W round program — the uint8-wire augment on
        # the elastic path, outside every banked elastic_w* twin.  A
        # width change changes the feed geometry, so the hook's jitted
        # augment compiles once per width (like the round program).
        self.feed_device_fn = None
        self._average = jax.jit(
            lambda v: jax.tree_util.tree_map(lambda x: x.mean(0), v))

    # -- mesh / program construction ---------------------------------------

    def _mesh_for(self, width: int):
        if width not in self._programs:
            mesh = sized_data_mesh(width, self._devices)
            self._programs[width] = (mesh, self._make_round(mesh))
        return self._programs[width][0]

    def _program(self, width: int):
        self._mesh_for(width)
        return self._programs[width][1]

    def _make_round(self, mesh):
        """The jitted weighted τ-averaging round for one mesh width:
        tau local solver steps per worker (the same scan body as
        ``ParallelTrainer._local_tau_steps``), then the WEIGHTED model
        average ``x̄ = Σ w_i x_i / Σ w_i`` — with every weight 1.0 this
        is exactly the plain pmean round (``Σ x_i / W``), which is what
        the s=0 staleness test pins; a rejoining straggler enters with
        ``w = decay**s < 1``.  Slots stay per-worker, like the tau mode
        (the consensus a joiner adopts is formed host-side)."""
        axis = self._axis
        step = self._step_fn
        in_specs = (P(axis), P(axis), P(axis), P(), P(None, axis), P())
        out_specs = (P(axis), P(axis), P())
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)

        def round_fn(variables, slots, weights, it, feeds, key):
            def body(v_blk, s_blk, w_blk, it_, feeds_blk, key_):
                sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
                v, sl = sq(v_blk), sq(s_blk)
                wkey = jax.random.fold_in(key_, jax.lax.axis_index(axis))

                def one(carry, feed):
                    v, sl, i = carry
                    v, sl, loss = step(v, sl, i, feed, wkey)
                    return (v, sl, i + 1), loss

                (v, sl, _), losses = jax.lax.scan(
                    one, (v, sl, it_), feeds_blk)
                w = w_blk[0]
                wsum = jax.lax.psum(w, axis)

                def wavg(x):
                    if not jnp.issubdtype(x.dtype, jnp.floating):
                        # integer state leaves (none in the zoo today)
                        # keep the tau mode's plain pmean semantics
                        return jax.lax.pmean(x, axis)
                    return (jax.lax.psum(x * w.astype(x.dtype), axis)
                            / wsum.astype(x.dtype))

                v = jax.tree_util.tree_map(wavg, v)
                loss = jax.lax.pmean(jnp.mean(losses), axis)
                return ex(v), ex(sl), loss

            return shard_map(
                body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            )(variables, slots, weights, it, feeds, key)

        return jax.jit(round_fn, donate_argnums=(0, 1))

    # -- placement ---------------------------------------------------------

    def _place(self, stacked, mesh):
        spec = NamedSharding(mesh, P(self._axis))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), spec), stacked)

    def _place_feeds(self, feeds: dict, mesh) -> dict:
        spec = NamedSharding(mesh, P(None, self._axis))
        return {k: jax.device_put(jnp.asarray(v), spec)
                for k, v in feeds.items()}

    # -- data --------------------------------------------------------------

    def _round_feeds(self, data_fn: ShardFn, width: int) -> dict:
        """[tau, width * b, ...] feeds assembled under the modulo
        ownership rule — axis-1 block ``w`` is worker ``w``'s batch."""
        grid = round_shards(self.cursor, self.tau, width)
        steps = []
        for t in range(self.tau):
            per_worker = [data_fn(int(g)) for g in grid[t]]
            steps.append({
                k: np.concatenate([np.asarray(f[k]) for f in per_worker])
                for k in per_worker[0]})
        return {k: np.stack([s[k] for s in steps]) for k in steps[0]}

    # -- membership --------------------------------------------------------

    def _emit_member(self, event: str, **fields) -> None:
        rec = get_recorder()
        if rec:
            rec.emit(event, **fields)

    def _apply_boundary(self, rnd: int) -> None:
        """Apply rejoins due + the plan's events for round ``rnd``; on
        any width change, re-form the mesh and re-place the survivors'
        state (blob-wise host trees — the checkpoint representation)."""
        due = [p for p in self._parked if p.rejoin_round <= rnd]
        events = self.plan.at(rnd)
        if not due and not events:
            self._round_weights = np.ones((self.width,), np.float32)
            return

        # pool state, blob-wise, at entry to the boundary
        host_v = jax.device_get(self.variables)
        host_s = jax.device_get(self.slots)
        rows = [
            {"wid": self._wids[i],
             "v": _tree_row(host_v, i), "s": _tree_row(host_s, i),
             "weight": 1.0}
            for i in range(self.width)
        ]
        # a departing worker's params+slots fold into the consensus a
        # joiner adopts: capture the entry pool (kills included) here
        entry_slot_rows = [r["s"] for r in rows]
        entry_param_rows = [r["v"] for r in rows]
        from_width = self.width

        for ev in events:
            if ev.kind == "kill":
                match = [r for r in rows if r["wid"] == ev.worker]
                if not match:
                    raise ValueError(
                        f"FaultPlan kills worker {ev.worker} at round "
                        f"{rnd} but it is not active (active ids: "
                        f"{[r['wid'] for r in rows]})")
                if len(rows) == 1:
                    raise ValueError(
                        "FaultPlan would kill the last active worker")
                rows.remove(match[0])
                self._emit_member(
                    "worker_lost", worker=ev.worker, round=rnd,
                    width=len(rows), reason="killed (fault plan)")
            elif ev.kind == "delay":
                match = [r for r in rows if r["wid"] == ev.worker]
                if not match:
                    raise ValueError(
                        f"FaultPlan delays worker {ev.worker} at round "
                        f"{rnd} but it is not active")
                if len(rows) == 1:
                    raise ValueError(
                        "FaultPlan would park the last active worker")
                rows.remove(match[0])
                missed = max(1, math.ceil(ev.steps / self.tau))
                self._parked.append(_Parked(
                    wid=ev.worker, variables=match[0]["v"],
                    slots=match[0]["s"], parked_round=rnd,
                    rejoin_round=rnd + missed))
                self._emit_member(
                    "worker_lost", worker=ev.worker, round=rnd,
                    width=len(rows),
                    reason=f"straggler: {ev.steps} step(s) "
                           f"(~{missed} round(s)) behind")
            elif ev.kind == "join":
                for _ in range(ev.count):
                    wid = self._next_wid
                    self._next_wid += 1
                    rows.append({
                        "wid": wid,
                        "v": _tree_mean(entry_param_rows),
                        "s": _tree_mean(entry_slot_rows),
                        "weight": 1.0})
                    self._emit_member(
                        "worker_joined", worker=wid, round=rnd,
                        width=len(rows), staleness=0, weight=1.0,
                        reason="joined fresh from consensus")

        # rejoins: stale replicas re-enter with damped weight, or are
        # dropped past the staleness bound (bounded-staleness contract:
        # nothing older than the bound ever enters the average)
        for p in due:
            self._parked.remove(p)
            s = rnd - p.parked_round
            if s > self.staleness_bound:
                self._emit_member(
                    "worker_lost", worker=p.wid, round=rnd,
                    width=len(rows), staleness=s,
                    reason=f"staleness {s} exceeds bound "
                           f"{self.staleness_bound}; contribution "
                           "dropped")
                continue
            weight = self.staleness_decay ** s
            rows.append({"wid": p.wid, "v": p.variables, "s": p.slots,
                         "weight": weight})
            self._emit_member(
                "worker_joined", worker=p.wid, round=rnd,
                width=len(rows), staleness=s, weight=float(weight),
                reason="straggler rejoined staleness-damped")

        new_width = len(rows)
        if not (1 <= new_width <= len(self._devices)):
            raise ValueError(
                f"round {rnd}: worker set of {new_width} does not fit "
                f"the device pool ({len(self._devices)})")
        self._wids = [r["wid"] for r in rows]
        self._round_weights = np.asarray(
            [r["weight"] for r in rows], np.float32)
        mesh = self._mesh_for(new_width)
        if new_width != from_width:
            self._emit_member(
                "mesh_resize", round=rnd, from_width=from_width,
                to_width=new_width, devices=new_width)
        self.width = new_width
        self.mesh = mesh
        self.variables = self._place(
            _tree_stack([r["v"] for r in rows]), mesh)
        self.slots = self._place(
            _tree_stack([r["s"] for r in rows]), mesh)

    # -- the round loop ----------------------------------------------------

    def train_round(self, data_fn: ShardFn) -> float:
        """One elastic round: apply the boundary's membership changes,
        run tau local steps per active worker, weighted-average.  With
        ``SPARKNET_OBS`` armed the round record carries mode
        ``elastic`` and the live worker count; membership changes are
        journaled as their own events."""
        rec = get_recorder()
        t0 = time.perf_counter() if rec else 0.0
        rnd = self.round
        # widths already compiled BEFORE the boundary: a round at a
        # fresh width builds its program by design, and its sentinel
        # record must say so (expected_compiles below)
        seen_widths = set(self._programs)
        self._apply_boundary(rnd)
        W = self.width
        feeds_np = self._round_feeds(data_fn, W)
        feeds = self._place_feeds(feeds_np, self.mesh)
        if self.feed_device_fn is not None:
            feeds = self.feed_device_fn(feeds, self.iter)
        weights = jax.device_put(
            jnp.asarray(self._round_weights),
            NamedSharding(self.mesh, P(self._axis)))
        self.variables, self.slots, loss = self._program(W)(
            self.variables, self.slots, weights, self.iter, feeds,
            self.solver._key)
        cursor0 = self.cursor
        self.iter += self.tau
        self.cursor += self.tau * W
        self.round += 1
        if rec:
            from sparknet_tpu.common import value_fence
            from sparknet_tpu.obs import lineage as obs_lineage

            loss_val = value_fence(loss)
            batch = next(
                (int(v.shape[1]) for v in feeds_np.values()
                 if getattr(v, "ndim", 0) > 1), 0)
            rec.round(
                mode="elastic", tau=self.tau, devices=W, workers=W,
                iters=self.tau, batch=batch,
                wall_s=time.perf_counter() - t0, loss=loss_val,
                fenced=True, comm=self._obs_comm(), iteration=self.iter,
                # the round's causal input: the global shard-id range
                # _round_feeds consumed (round_shards' grid) — minted
                # host-side from the deterministic cursor, never enters
                # the round program
                lineage=obs_lineage.round_lineage(
                    "elastic", rnd, cursor0, cursor0 + self.tau * W - 1),
                expected_compiles=W not in seen_widths)
            return loss_val
        return float(loss)

    def train(self, num_rounds: int, data_fn: ShardFn,
              callback=None) -> float:
        loss = 0.0
        for _ in range(num_rounds):
            loss = self.train_round(data_fn)
            if callback:
                callback(self.round, loss)
        return loss

    def _obs_comm(self) -> dict | None:
        """The width-parameterized comm expectation for the CURRENT
        round (re-derived on resize — the predicted budget is per-model,
        not per-width, but the note names the width)."""
        from sparknet_tpu.analysis.comm_model import expected_comm

        def tree_bytes(tree) -> int:
            return sum(
                int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(tree)
                if hasattr(l, "shape") and hasattr(l, "dtype"))

        cache = getattr(self, "_obs_comm_cache", {})
        if self.width in cache:
            return cache[self.width]
        pb = tree_bytes(self.solver.variables.params)
        sb = tree_bytes(self.solver.variables.state)
        try:
            exp = expected_comm(f"elastic_w{self.width}", param_bytes=pb,
                                state_bytes=sb)
            comm: dict | None = {
                "param_bytes": pb, "state_bytes": sb,
                "predicted": {k: (list(v) if v is not None else None)
                              for k, v in exp.required.items()},
                "note": exp.note,
            }
        except KeyError:  # pragma: no cover - elastic is always modeled
            comm = None
        cache[self.width] = comm
        self._obs_comm_cache = cache
        return comm

    # -- state surface (blob-wise — the checkpoint representation) ---------

    def state_dict(self) -> dict:
        """The live pool, blob-wise on host: enough to seed another
        ElasticTrainer (the restart-equivalence gate) or to persist.
        Parked stragglers ride along so a resumed run owes them the
        same rejoin."""
        host_v = jax.device_get(self.variables)
        host_s = jax.device_get(self.slots)
        return {
            "width": self.width,
            "wids": list(self._wids),
            "next_wid": self._next_wid,
            "variables": jax.tree_util.tree_map(np.asarray, host_v),
            "slots": jax.tree_util.tree_map(np.asarray, host_s),
            "iter": self.iter,
            "round": self.round,
            "cursor": self.cursor,
            "parked": list(self._parked),
        }

    def load_state_dict(self, state: dict) -> None:
        width = int(state["width"])
        if not (1 <= width <= len(self._devices)):
            raise ValueError(
                f"state width {width} does not fit the device pool")
        self.width = width
        self._wids = list(state["wids"])
        self._next_wid = int(state["next_wid"])
        self.mesh = self._mesh_for(width)
        self.variables = self._place(state["variables"], self.mesh)
        self.slots = self._place(state["slots"], self.mesh)
        self.iter = int(state["iter"])
        self.round = int(state["round"])
        self.cursor = int(state["cursor"])
        self._parked = list(state.get("parked", []))
        self._round_weights = np.ones((width,), np.float32)

    # -- consensus surface -------------------------------------------------

    def _averaged_variables(self) -> NetVars:
        return self._average(self.variables)

    def get_weights(self) -> WeightCollection:
        """Driver-visible consensus model (replicas are equal right
        after a round; mid-boundary the mean is the consensus)."""
        return variables_to_collection(
            jax.tree_util.tree_map(np.asarray, self._averaged_variables()))

    def sync_to_solver(self) -> None:
        """Fold the pool back into the wrapped Solver (averaged params
        and state; slots averaged like the tau mode's sync)."""
        self.solver.variables = jax.tree_util.tree_map(
            np.asarray, self._averaged_variables())
        self.solver.slots = jax.tree_util.tree_map(
            np.asarray, self._average(self.slots))
        self.solver.iter = self.iter
