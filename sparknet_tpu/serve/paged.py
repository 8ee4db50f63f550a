"""Paged KV-cache decode: token serving stops paying O(seq_len) per token.

The rectangle decoder (serve/continuous.py) holds the line on admission
mechanics but pays twice for having no cache: every decode step reruns
the FULL [slots, seq_len] forward (O(seq_len) recompute per emitted
token), and a 32-token request reserves exactly the HBM a 2048-token
one would — capacity is priced at the worst case, always.  This module
is the cached engine (ISSUE 19, ROADMAP item 4):

* **Block pool** — K/V live in fixed-size blocks inside shared
  ``[n_attn_layers, num_blocks, block_tokens, H, D]`` arenas.  A free
  list hands blocks out; each slot owns a small int32 block TABLE
  instead of a contiguous rectangle.  Block 0 is the null block —
  inactive/overflow table entries point at it, and the attention mask
  guarantees its garbage contributes exactly 0.0 to any live row.
  The pool keeps a zero-leak ledger: over any drained run,
  ``allocated - freed == 0`` or the run is a bug.

* **Prefill/decode disaggregation** — a prompt is ONE full-window
  forward (``models/zoo.build_prefill``: the ordinary causal program,
  also writing K/V through the tables) riding a small AOT bucket
  ladder; every subsequent token is ONE cached step
  (``models/zoo.build_decode_step`` → ``paged_attention``) over the
  slot arena.  Both sides are AOT-compiled in ``__init__``, so the
  recompile sentinel stays at zero after warmup, and both are priced
  BEFORE any compile: params + pool + arena bytes against the usable-
  HBM budget (``AdmissionRefused`` on a predicted miss — the
  serve/residency.py stance extended to the decode plane).

* **Exactness** — every row's decode output is a pure function of its
  own (token, position, table): masked columns are -1e30 BEFORE the
  softmax, so unwritten cache lines, the null block, and neighbour
  slots contribute nothing.  Paged decode interleaved with arbitrary
  neighbours therefore produces the SAME greedy continuation as
  decoded alone, and the same token ids as the rectangle
  ``ContinuousDecoder`` (tests/test_paged.py pins both; CPU compiles
  pin single-thread Eigen like the engine's EXACT gate).  The
  rectangle stays the default path — nothing here is reachable unless
  constructed.

Speculative decoding is the declared seam, not scope: the decode step's
token axis is [B, W] and ``build_decode_step(proposed_width=...)``
refuses W > 1 until the next PR lowers it.

ref: apps/FeaturizerApp.scala:1 (the reference's batch scoring — RDD
granularity; paged slot-level decode is new TPU-first surface).
"""

from __future__ import annotations

import collections
import itertools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.analysis.mem_model import HBM_USABLE_FRAC, V5E_HBM_BYTES
from sparknet_tpu.serve.batcher import Ticket
from sparknet_tpu.serve.engine import (
    AdmissionRefused,
    _exactness_compiler_options,
)

__all__ = [
    "BlockPool",
    "PagedDecoder",
    "PoolExhausted",
    "TokenRouter",
    "build_decode_program",
    "build_rect_program",
    "capacity_ratio",
    "pool_bytes",
]


class PoolExhausted(RuntimeError):
    """An allocation the free list cannot cover (admission backpressure,
    not an error path — the decoder keeps the request queued)."""


class BlockPool:
    """Free-list block allocator with an exact zero-leak ledger.

    Block 0 is the NULL block: never allocated, never freed — the
    landing zone every inactive table entry points at.  ``alloc`` is
    all-or-nothing (a partially allocated request could deadlock the
    arena at full occupancy); ``free`` refuses double-frees and foreign
    ids loudly, because a silent one is how a pool leaks.
    """

    def __init__(self, num_blocks: int, block_tokens: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (null + 1 usable), got {num_blocks}")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {block_tokens}")
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        # LIFO free list over 1..N-1; block 0 is the null block
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._owned: set[int] = set()
        self.allocated = 0
        self.freed = 0

    def available(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return len(self._owned)

    def alloc(self, n: int) -> list[int]:
        if n <= 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free of "
                f"{self.num_blocks - 1} usable")
        blocks = [self._free.pop() for _ in range(n)]
        self._owned.update(blocks)
        self.allocated += n
        return blocks

    def free(self, blocks) -> None:
        blocks = list(blocks)
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is the null block — never freed")
            if b not in self._owned:
                raise ValueError(
                    f"block {b} is not allocated (double-free or foreign id)")
        for b in blocks:
            self._owned.discard(b)
            self._free.append(b)
        self.freed += len(blocks)

    def ledger(self) -> dict:
        """The zero-leak ledger: at quiesce (nothing in use),
        ``leaked`` MUST be 0."""
        return {
            "allocated": self.allocated,
            "freed": self.freed,
            "in_use": len(self._owned),
            "leaked": self.allocated - self.freed - len(self._owned),
        }


def pool_bytes(n_attn: int, num_blocks: int, block_tokens: int,
               heads: int, head_dim: int, itemsize: int = 4) -> int:
    """Exact K+V arena bytes — the paged plane's admission price."""
    return 2 * n_attn * num_blocks * block_tokens * heads * head_dim * itemsize


def capacity_ratio(seq_len: int, block_tokens: int, totals) -> float:
    """Concurrent-sequence capacity of paged vs rectangle KV residency
    at equal HBM (the byte model behind the >= 2x acceptance claim).

    A rectangle cache reserves ``seq_len`` cache lines per slot no
    matter the request (worst-case pricing); paged reserves
    ``ceil(total / T) * T`` lines — proportional to the request's own
    length, rounded up to whole blocks.  The ratio of the two
    per-sequence reservations IS the admission-capacity ratio, because
    both planes spend the same bytes per cache line.  ``totals`` are
    per-request total lengths (prompt + generated)."""
    totals = [int(t) for t in totals]
    if not totals:
        raise ValueError("capacity_ratio needs at least one request")
    paged = sum(math.ceil(t / block_tokens) * block_tokens
                for t in totals) / len(totals)
    return float(seq_len) / paged


class _Gen:
    __slots__ = ("ticket", "ids", "n_prompt", "remaining", "blocks",
                 "t_first", "t_prev", "deltas_ms")

    def __init__(self, ticket: Ticket, ids: list[int], remaining: int,
                 blocks: list[int]):
        self.ticket = ticket
        self.ids = ids
        self.n_prompt = len(ids)
        self.remaining = remaining
        self.blocks = blocks
        self.t_first: float | None = None
        self.t_prev: float | None = None
        self.deltas_ms: list[float] = []


class PagedDecoder:
    """Greedy decode over a block-paged KV cache: prefill rides an AOT
    bucket ladder, decode rides a fixed [slots] arena of single-token
    cached steps.  API mirrors ``ContinuousDecoder`` (submit / pending /
    active / step / run / stats) so the two arms A/B cleanly.

    ``num_blocks`` defaults to full capacity (every slot can hold
    ``seq_len`` tokens) so exactness gates never see pool backpressure;
    benches pass a smaller pool to exercise the capacity lever.
    Requests with ``n_prompt + max_new > seq_len`` are refused at
    submit: RoPE positions are absolute, so a paged cache line is valid
    only while the sequence never slides (the rectangle's sliding
    window is exactly the recompute this engine exists to delete).
    """

    def __init__(self, slots: int = 8, seq_len: int = 32,
                 vocab: int = 64, embed_dim: int = 32, heads: int = 4,
                 ffn_dim: int = 64, blocks: int = 1, seed: int = 0,
                 variables=None, device=None, block_tokens: int = 8,
                 num_blocks: int | None = None,
                 hbm_bytes: int = V5E_HBM_BYTES,
                 usable_frac: float = HBM_USABLE_FRAC,
                 recorder=None, run_id: str = "paged"):
        from sparknet_tpu.common import Phase
        from sparknet_tpu.compiler.graph import Network
        from sparknet_tpu.models.zoo import (
            build_decode_step, build_prefill, charlm, decode_spec)
        from sparknet_tpu.obs.recorder import get_recorder

        if slots < 2:
            # mirrors the engine's EXEC_FLOOR (serve/continuous.py)
            raise ValueError(f"need >= 2 slots, got {slots}")
        self.slots = int(slots)
        self.seq_len = int(seq_len)
        self.vocab = int(vocab)
        self.block_tokens = int(block_tokens)
        self.device = device
        self._rec = recorder if recorder is not None else get_recorder()
        self._run_id = run_id
        net = charlm(batch=self.slots, seq_len=self.seq_len,
                     vocab=self.vocab, embed_dim=embed_dim,
                     heads=heads, ffn_dim=ffn_dim, blocks=blocks)
        self.network = Network(net, Phase.TEST)
        self.spec = decode_spec(self.network)
        self.variables = (self.network.init(jax.random.key(seed))
                          if variables is None else variables)
        if device is not None:
            self.variables = jax.device_put(self.variables, device)

        # table width: the most blocks any request can ever need
        self.blocks_per_slot = math.ceil(self.seq_len / self.block_tokens)
        if num_blocks is None:
            num_blocks = 1 + self.slots * self.blocks_per_slot
        self.pool = BlockPool(num_blocks, self.block_tokens)

        # -- admission pricing BEFORE any compile (the residency stance
        # extended to the decode plane: a refusal costs nothing, an OOM
        # mid-serve costs the window) --------------------------------
        params_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(self.variables)
            if hasattr(l, "shape"))
        self.pool_hbm_bytes = pool_bytes(
            len(self.spec.attn_layers), num_blocks, self.block_tokens,
            self.spec.heads, self.spec.head_dim)
        predicted = params_bytes + self.pool_hbm_bytes
        budget = int(hbm_bytes * usable_frac)
        if predicted > budget:
            verdict = {
                "family": "charlm", "max_bucket": self.slots,
                "predicted_bytes": predicted, "resident_bytes": 0,
                "budget_bytes": budget, "priced": True, "fits": False,
            }
            if self._rec:
                self._rec.emit(
                    "token", kind="admission_refused",
                    note=self._run_id,
                    predicted_bytes=predicted, budget_bytes=budget,
                    blocks_total=num_blocks - 1)
            raise AdmissionRefused(verdict)

        A = len(self.spec.attn_layers)
        H, D = self.spec.heads, self.spec.head_dim
        self._k_pool = jnp.zeros(
            (A, num_blocks, self.block_tokens, H, D), jnp.float32)
        self._v_pool = jnp.zeros_like(self._k_pool)
        if device is not None:
            self._k_pool = jax.device_put(self._k_pool, device)
            self._v_pool = jax.device_put(self._v_pool, device)
        self._tables = np.zeros((self.slots, self.blocks_per_slot),
                                np.int32)

        # -- AOT programs (all compiles land HERE; the sentinel must
        # read zero across every later step) -------------------------
        # buffer donation threads the pools through without a copy, but
        # the CPU backend can't donate (jax warns and ignores) — and
        # the exactness gates RUN on CPU, so gate it on the backend
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        step_fn = build_decode_step(self.network)
        prefill_fn = build_prefill(self.network)
        sharding = (jax.sharding.SingleDeviceSharding(device)
                    if device is not None else None)

        def _sds(shape, dtype=np.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        pool_sds = jax.ShapeDtypeStruct(
            self._k_pool.shape, np.float32, sharding=sharding)
        t0 = time.perf_counter()
        self._decode_exec = jax.jit(
            step_fn, donate_argnums=donate).lower(
                self.variables, pool_sds, pool_sds,
                _sds((self.slots, 1)), _sds((self.slots,)),
                _sds((self.slots, self.blocks_per_slot))).compile(
                    compiler_options=_exactness_compiler_options())
        # prefill ladder: power-of-two row buckets up to the slot count
        # (engine-ladder shape; a 1-row prefill rides the 2-bucket —
        # the EXEC_FLOOR reduction-order rule)
        buckets = [b for b in (2, 4, 8, 16, 32, 64) if b < self.slots]
        self.prefill_buckets = tuple(buckets) + (self.slots,)
        self._prefill_exec = {}
        for pb in self.prefill_buckets:
            self._prefill_exec[pb] = jax.jit(
                prefill_fn, donate_argnums=(
                    () if not donate else (3, 4))).lower(
                    self.variables, _sds((pb, self.seq_len)),
                    _sds((pb,)), pool_sds, pool_sds,
                    _sds((pb, self.blocks_per_slot))).compile(
                        compiler_options=_exactness_compiler_options())
        self.compile_wall_s = time.perf_counter() - t0

        self._ids = itertools.count()
        self._waiting: collections.deque[_Gen] = collections.deque()
        self._active: dict[int, _Gen] = {}
        self._free_slots = list(range(self.slots - 1, -1, -1))
        self.steps = 0
        self.prefills = 0
        self.admitted = 0
        self.completed = 0
        self.decode_path_compiles = 0

    # -- submit side -------------------------------------------------------

    def submit(self, prompt_ids, max_new: int) -> Ticket:
        """Queue one generation; the Ticket resolves with the greedy
        continuation (int list of length ``max_new``)."""
        prompt = [int(i) for i in prompt_ids]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if any(not 0 <= i < self.vocab for i in prompt):
            raise ValueError(f"prompt ids outside [0, {self.vocab})")
        if max_new <= 0:
            raise ValueError(f"max_new must be positive, got {max_new}")
        if len(prompt) + max_new > self.seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"the {self.seq_len}-token context — the paged cache "
                "never slides (absolute RoPE positions)")
        ticket = Ticket(next(self._ids), prompt, time.monotonic())
        self._waiting.append(_Gen(ticket, prompt, int(max_new), []))
        return ticket

    def pending(self) -> int:
        return len(self._waiting)

    def active(self) -> int:
        return len(self._active)

    # -- decode loop -------------------------------------------------------

    def _retire(self, slot: int) -> None:
        st = self._active.pop(slot)
        st.ticket.resolve(result=st.ids[st.n_prompt:])
        self.pool.free(st.blocks)
        self._tables[slot] = 0
        self._free_slots.append(slot)
        self.completed += 1
        if self._rec:
            d = sorted(st.deltas_ms)
            now = time.monotonic()
            self._rec.emit(
                "token", kind="request", note=self._run_id,
                tokens=len(st.ids) - st.n_prompt,
                prompt_tokens=st.n_prompt,
                ttft_ms=round((st.t_first - st.ticket.t_submit) * 1e3, 3),
                total_ms=round((now - st.ticket.t_submit) * 1e3, 3),
                inter_token_p50_ms=(
                    round(d[len(d) // 2], 3) if d else 0.0),
                inter_token_max_ms=round(d[-1], 3) if d else 0.0)

    def _admit(self) -> list[int]:
        """Slot-level admission with block-level pricing: a request
        enters only when BOTH a slot row and its whole block budget
        (``ceil((n_prompt + max_new) / T)``, allocated up front so a
        mid-flight generation can never die of pool exhaustion) are
        free.  FIFO without skipping — a large request at the head
        waits for blocks rather than being starved by small ones."""
        newly: list[int] = []
        while self._free_slots and self._waiting:
            st = self._waiting[0]
            need = math.ceil(
                (st.n_prompt + st.remaining) / self.block_tokens)
            try:
                blocks = self.pool.alloc(need)
            except PoolExhausted:
                break
            self._waiting.popleft()
            st.blocks = blocks
            slot = self._free_slots.pop()
            self._active[slot] = st
            self._tables[slot] = 0
            self._tables[slot, :need] = blocks
            newly.append(slot)
        self.admitted += len(newly)
        return newly

    def _prefill(self, slots: list[int]) -> int:
        """One ladder-bucket prefill over the newly admitted rows:
        writes their prompt K/V through the tables and emits each
        row's FIRST generated token.  Returns tokens produced."""
        from sparknet_tpu.obs.sentinel import get_sentinel

        pb = next(b for b in self.prefill_buckets if b >= len(slots))
        tokens = np.zeros((pb, self.seq_len), np.int32)
        lengths = np.ones((pb,), np.int32)  # pad rows: length 1, null
        tables = np.zeros((pb, self.blocks_per_slot), np.int32)
        for i, s in enumerate(slots):
            st = self._active[s]
            tokens[i, :st.n_prompt] = st.ids
            lengths[i] = st.n_prompt
            tables[i] = self._tables[s]
        sentinel = get_sentinel()
        compiles0 = sentinel.thread_count()
        t0 = time.monotonic()
        self._k_pool, self._v_pool, last = self._prefill_exec[pb](
            self.variables, tokens, lengths, self._k_pool,
            self._v_pool, tables)
        last = np.asarray(last)
        self.decode_path_compiles += sentinel.thread_count() - compiles0
        self.prefills += 1
        now = time.monotonic()
        produced = 0
        for i, s in enumerate(slots):
            st = self._active[s]
            st.ids.append(int(np.argmax(last[i])))
            st.remaining -= 1
            st.t_first = now
            st.t_prev = now
            produced += 1
            if st.remaining == 0:
                self._retire(s)
        if self._rec:
            self._rec.emit(
                "token", kind="prefill", note=self._run_id,
                rows=len(slots), bucket=pb,
                prompt_tokens=int(sum(lengths[:len(slots)])),
                wall_ms=round((now - t0) * 1e3, 3),
                blocks_free=self.pool.available(),
                blocks_total=self.pool.num_blocks - 1)
        return produced

    def step(self) -> int:
        """One engine tick: admit + prefill new rows, then ONE cached
        decode step over the arena.  Returns tokens produced."""
        from sparknet_tpu.obs.sentinel import get_sentinel

        produced = 0
        newly = self._admit()
        if newly:
            produced += self._prefill(newly)
        if not self._active:
            return produced
        tokens = np.zeros((self.slots, 1), np.int32)
        positions = np.zeros((self.slots,), np.int32)
        for s, st in self._active.items():
            tokens[s, 0] = st.ids[-1]
            positions[s] = len(st.ids) - 1
        sentinel = get_sentinel()
        compiles0 = sentinel.thread_count()
        self._k_pool, self._v_pool, logits = self._decode_exec(
            self.variables, self._k_pool, self._v_pool, tokens,
            positions, self._tables)
        logits = np.asarray(logits)
        self.decode_path_compiles += sentinel.thread_count() - compiles0
        self.steps += 1
        now = time.monotonic()
        for s in list(self._active):
            st = self._active[s]
            st.ids.append(int(np.argmax(logits[s, 0])))
            st.remaining -= 1
            produced += 1
            if st.t_prev is not None:
                st.deltas_ms.append((now - st.t_prev) * 1e3)
            st.t_prev = now
            if st.remaining == 0:
                self._retire(s)
        return produced

    def run(self, max_steps: int = 10_000) -> int:
        """Step until every queued request completes; returns tokens
        produced.  ``max_steps`` is a runaway bound, not a policy."""
        produced = 0
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and not self._waiting:
                self._emit_summary()
                return produced
            produced += n
        raise RuntimeError(
            f"decode did not drain within {max_steps} steps "
            f"({len(self._waiting)} waiting, {len(self._active)} "
            "active)")

    def _emit_summary(self) -> None:
        if not self._rec:
            return
        ledger = self.pool.ledger()
        self._rec.emit(
            "token", kind="summary", note=self._run_id,
            requests=self.completed, steps=self.steps,
            prefills=self.prefills, compiles=self.decode_path_compiles,
            allocated=ledger["allocated"], freed=ledger["freed"],
            leaked=ledger["leaked"], dropped=0,
            blocks_total=self.pool.num_blocks - 1,
            blocks_free=self.pool.available())

    def stats(self) -> dict:
        return {
            "slots": self.slots, "seq_len": self.seq_len,
            "block_tokens": self.block_tokens,
            "blocks_total": self.pool.num_blocks - 1,
            "pool_hbm_bytes": self.pool_hbm_bytes,
            "steps": self.steps, "prefills": self.prefills,
            "admitted": self.admitted, "completed": self.completed,
            "decode_path_compiles": self.decode_path_compiles,
            "ledger": self.pool.ledger(),
        }


class TokenRouter:
    """Token-serving face of the pod router (serve/router.py): K
    ``PagedDecoder`` replicas, least-projected-work routing, a fair
    one-step-per-replica sweep, and the zero-drop ledger
    (``submitted - resolved`` must be 0 over any drained run).
    Single-threaded by construction — the sweep IS the scheduler, so
    there is no lock plane for conccheck to audit."""

    def __init__(self, replicas: int = 2, **decoder_kwargs):
        if replicas < 1:
            raise ValueError(f"need >= 1 replica, got {replicas}")
        run_id = decoder_kwargs.pop("run_id", "token_router")
        self.decoders = [
            PagedDecoder(run_id=f"{run_id}/r{i}", **decoder_kwargs)
            for i in range(replicas)
        ]
        self.submitted = 0
        self._tickets: list[Ticket] = []
        self._sweep = 0

    def _projected_work(self, d: PagedDecoder) -> int:
        """Tokens this replica is still committed to emit — the
        router.py projected-wait idea with drain-rate folded out
        (replicas are homogeneous AOT programs)."""
        work = sum(st.remaining for st in d._active.values())
        work += sum(st.remaining for st in d._waiting)
        return work

    def submit(self, prompt_ids, max_new: int) -> Ticket:
        d = min(self.decoders, key=self._projected_work)
        ticket = d.submit(prompt_ids, max_new)
        self.submitted += 1
        self._tickets.append(ticket)
        return ticket

    def sweep(self) -> int:
        """One fair pass: every replica gets exactly one step, rotated
        so no replica is systematically first."""
        n = len(self.decoders)
        produced = 0
        for i in range(n):
            produced += self.decoders[(self._sweep + i) % n].step()
        self._sweep = (self._sweep + 1) % n
        return produced

    def run(self, max_steps: int = 10_000) -> int:
        produced = 0
        for _ in range(max_steps):
            n = self.sweep()
            if n == 0 and not any(d.pending() for d in self.decoders):
                return produced
            produced += n
        raise RuntimeError(f"router did not drain within {max_steps} sweeps")

    def resolved(self) -> int:
        return sum(1 for t in self._tickets if t.done())

    def ledger(self) -> dict:
        dropped = self.submitted - self.resolved()
        pool = {"allocated": 0, "freed": 0, "in_use": 0, "leaked": 0}
        for d in self.decoders:
            for k, v in d.pool.ledger().items():
                pool[k] += v
        return {"submitted": self.submitted,
                "resolved": self.resolved(), "dropped": dropped,
                "pool": pool}

    def stats(self) -> dict:
        return {"replicas": len(self.decoders),
                "ledger": self.ledger(),
                "decoders": [d.stats() for d in self.decoders]}


# ---------------------------------------------------------------------------
# Contract-twin programs (parallel/modes.py decode_* modes).
# ---------------------------------------------------------------------------


def build_rect_program(slots: int = 4, seq_len: int = 32):
    """The rectangle decoder's arena forward as TraceTarget material
    (``decode_rect``): the exact program ``ContinuousDecoder``
    AOT-compiles — full [slots, seq_len] forward to the LM head."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.models.zoo import charlm

    network = Network(charlm(batch=slots, seq_len=seq_len, vocab=64,
                             embed_dim=32, heads=4, ffn_dim=64,
                             blocks=1), Phase.TEST)
    variables = network.init(jax.random.key(0))

    def forward(vs, feeds):
        blobs, _, _ = network.apply(vs, feeds, rng=None, train=False,
                                    end="fc")
        return blobs["fc"]

    def feeds(seed: int):
        rs = np.random.RandomState(seed)
        return {
            "data": rs.randint(0, 64, (slots, seq_len)).astype(np.int32),
            "label": np.zeros((slots, seq_len), np.int32),
        }

    return jax.jit(forward), variables, feeds(0), feeds(1)


def build_decode_program(occupancy: int, slots: int = 4,
                         seq_len: int = 32, block_tokens: int = 8):
    """The cached decode step as TraceTarget material
    (``decode_paged_o<occupancy>``).  Occupancy changes only the DATA
    (how many rows carry live tables/positions), never a shape — so
    every occupancy twin must lower to the byte-identical StableHLO,
    which is the shape-stability contract (zero post-warmup compiles at
    any occupancy) made machine-checkable.  Returns ``(fn, args,
    alt_args, meta)``; the pools are the carry (donated argnums 1-2,
    first 2 flattened outputs)."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.models.zoo import build_decode_step, charlm, decode_spec

    if not 1 <= occupancy <= slots:
        raise ValueError(f"occupancy {occupancy} not in [1, {slots}]")
    network = Network(charlm(batch=slots, seq_len=seq_len, vocab=64,
                             embed_dim=32, heads=4, ffn_dim=64,
                             blocks=1), Phase.TEST)
    spec = decode_spec(network)
    variables = network.init(jax.random.key(0))
    mb = math.ceil(seq_len / block_tokens)
    num_blocks = 1 + slots * mb
    A = len(spec.attn_layers)
    k_pool = jnp.zeros((A, num_blocks, block_tokens, spec.heads,
                        spec.head_dim), jnp.float32)
    v_pool = jnp.zeros_like(k_pool)

    def args_at(seed: int):
        rs = np.random.RandomState(seed)
        tokens = np.zeros((slots, 1), np.int32)
        positions = np.zeros((slots,), np.int32)
        tables = np.zeros((slots, mb), np.int32)
        for s in range(occupancy):
            tables[s] = 1 + s * mb + np.arange(mb)
            positions[s] = rs.randint(0, seq_len)
            tokens[s, 0] = rs.randint(0, 64)
        return (variables, k_pool, v_pool, tokens, positions, tables)

    fn = jax.jit(build_decode_step(network), donate_argnums=(1, 2))
    meta = {
        "family": "charlm", "mesh": {}, "tau": 1, "batch": slots,
        "dtype": "f32", "layout": "nchw", "serve": True,
        "decode": "paged", "occupancy": int(occupancy),
        "block_tokens": int(block_tokens),
        "num_blocks": int(num_blocks),
        "pool_bytes": pool_bytes(A, num_blocks, block_tokens,
                                 spec.heads, spec.head_dim),
    }
    return fn, args_at(0), args_at(1), meta
