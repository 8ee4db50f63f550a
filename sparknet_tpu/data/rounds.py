"""The trainer's feeds: per-worker host batches side by side in one
persistent buffer, a whole tau-round (``stack_tau``) or one global batch
(``widen_batch``) at a time.

**The host-buffer rule.**  A feed that owns the host memory it fills
rewrites it only when nothing reads it any more.  jax keeps the numpy
source of a ``device_put`` immutable until the transfer completes, and
where ``device_put`` may alias host memory (the CPU backend) a placed
array IS its source for as long as it lives.  Four holders keep the rule,
each by its own means:

* ``prefetch.DevicePrefetcher``'s ring: ``RING`` host batches the feed
  thread owns.  A slot is refilled once the arrays placed from it a whole
  read ago are ready (``_transferred``); on an aliasing backend
  (``_aliases_host``) there is no ring and every batch is a fresh array.
* ``stack_tau``'s three ``RoundBuffer``s: its feed thread owns them and
  is exactly one round ahead of what it was asked for.
  ``ParallelTrainer.train_round`` asks for round n+1 and places it while
  round n trains, and has fenced round n-1 on ``float(loss)`` before
  round n began; so the buffer round n+2 is written into (round n-1's)
  has been transferred and, where a placed array aliases it, is read by
  nothing.  A fourth buffer would buy nothing, and two would let the
  thread write the round that is training.
* ``widen_batch``'s ``keep`` slots: the caller's thread owns them; a
  batch is rewritten ``keep`` calls later, after the same fence
  (``train_rounds`` holds a scan chunk's worth until it has stacked them).
* the process ring (``feed.process_feed``, trainer side): the shared-
  memory slots belong to the ring's workers, and a batch waits in a
  ``RoundBuffer`` past the ring's view-lifetime window, so the trainer's
  data fn takes stable copies (``as_data_fn(copy=True)``).
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from sparknet_tpu.data.prefetch import DONE, FeedThread, fresh_bytes
from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import feed_counts


# threads that touch a new buffer's pages (``RoundBuffer._make``): the
# host's cores, and no more than eight (past that the memory is the limit:
# a fresh 2 GB written twice takes 8 threads 0.72 s, 12 0.63; PERF.md, PR 29)
TOUCHERS = min(8, os.cpu_count() or 1)


class Turns:
    """A lock that serves its waiters in the order they came.  A
    ``threading.Lock`` goes to whoever asks first after a release, and
    that is the thread that just released it: of two feeds over one data
    fn the one with more reads to make starved the other (four reads in
    five, PERF.md, PR 27)."""

    def __init__(self):
        self._cv = threading.Condition()
        self._next = self._serving = 0  # tickets handed out, and served

    def __enter__(self):
        with self._cv:
            mine, self._next = self._next, self._next + 1
            self._cv.wait_for(lambda: self._serving == mine)

    def __exit__(self, *exc):
        with self._cv:
            self._serving += 1
            self._cv.notify_all()

    def locked(self) -> bool:
        return self._serving != self._next


_LOCKS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
_LOCKS_GUARD = threading.Lock()


def lock_of(data_fn) -> Turns:
    """THE lock calls into ``data_fn`` are made under, whoever asks: a
    ``Feed``'s own (its wrappers share it), a plain function's from a
    registry that forgets it with the function."""
    lock = getattr(data_fn, "lock", None)
    if lock is None:
        with _LOCKS_GUARD:
            lock = _LOCKS.setdefault(data_fn, Turns())
    return lock


class RoundBuffer:
    """ONE persistent host array ``[slots, workers * B, ...]`` per feed
    key, filled one per-worker batch at a time: batch (t, w) goes to the
    contiguous view ``buf[t, w*B:(w+1)*B]``.  A ``takes_out`` data fn is
    handed that view and writes its records straight into it; any other
    batch is copied there.  Nothing is concatenated or stacked.  The
    arrays are made on the first read, from the first batch's shapes (or
    by ``make_like``, from another buffer's), and live as long as
    the buffer's owner (``stack_tau`` / ``widen_batch``;
    the module docstring says when they may be rewritten).  The data fn
    is called under its own lock (``lock_of``).

    ``sn.feed.stack``, one per slot after the slot's reads, times what is
    left of the pack's own work and counts the slot's images;
    ``alloc_bytes`` is the buffer itself in the first one and 0 after."""

    def __init__(self, train_fn, slots, workers):
        self._fn, self._slots, self._workers = train_fn, slots, workers
        self._takes_out = getattr(train_fn, "takes_out", False)
        self._lock = lock_of(train_fn)
        self.arrays: dict = {}
        self._batch = 0  # B, known with the first batch
        self._strays: list = []  # (views, batch) that missed their views
        self._alloc = 0

    def read(self, index, t, w):
        """Batch ``index`` of the data fn into cell (t, w)."""
        views = self._views(t, w)
        with self._lock:
            got = (self._fn(index, out=views) if views and self._takes_out
                   else self._fn(index))
        if not views:
            self._make(got)
            views = self._views(t, w)
        if fresh_bytes(got, views):
            self._strays.append((views, got))

    def make_like(self, other):
        """The arrays now, for the batches ``other`` holds, and not on
        the first read."""
        self._make(other._views(0, 0))

    def _make(self, batch):
        self._batch = len(next(iter(batch.values())))
        self.arrays = {
            k: np.empty((self._slots, self._workers * self._batch,
                         *v.shape[1:]), v.dtype)
            for k, v in batch.items()}
        self._alloc = sum(a.nbytes for a in self.arrays.values())
        # the buffer's pages are touched here, not by the reads that
        # fill it: those hold the data fn's lock, and on the chip's
        # host a batch written into fresh memory takes 51 ms, into
        # memory written once 25, from then on 4 (PERF.md, PR 27), so
        # a second feed over the same data fn queued behind them.  From
        # a few threads at once: one writes a fresh 2 GB twice in 3.0 s,
        # eight in 0.7 (PERF.md, PR 29), and this is set-up time
        parts = [part for a in self.arrays.values()
                 for part in np.array_split(a.reshape(-1), TOUCHERS)]
        with ThreadPoolExecutor(TOUCHERS) as pool:
            for _ in range(2):
                list(pool.map(lambda part: part.fill(0), parts))

    def _views(self, t, w):
        lo = w * self._batch
        return {k: a[t, lo:lo + self._batch] for k, a in self.arrays.items()}

    def slot(self, it, t):
        """Slot ``t``, whole: ``{key: [workers * B, ...]}``."""
        feeds = {k: a[t] for k, a in self.arrays.items()}
        with get_recorder().span("sn.feed.stack", host=True, it=it,
                                 alloc_bytes=self._alloc, **feed_counts(feeds)):
            for views, got in self._strays:
                for k, v in got.items():
                    views[k][...] = v
        self._strays, self._alloc = [], 0
        return feeds


def stack_tau(train_fn, tau, num_workers):
    """[tau, B*workers, ...] feeds: the net batch is per-worker; each tau
    slot holds one batch per worker side by side (the global minibatch).
    Owns its own batch counter: each round consumes tau*num_workers fresh
    batches regardless of how the trainer advances its iteration count.

    The feed is always exactly ONE round ahead.  It owns three
    persistent buffers per feed key (``RoundBuffer``; all made and
    touched with the first batch, so none of that lands in a later
    round) and, from the first call on, one daemon thread
    (``prefetch.FeedThread``) that makes every call into the data fn, in
    the order and with the indices a serial pack would.  ``fn(it)`` hands
    out round n, waiting under ``sn.feed.wait`` where the thread has not
    filled it yet (``ready`` = 0), and only then lets the thread start on
    round n+1, in the buffer round n-2 was read from (the host-buffer
    rule, module docstring).  So the arrays ``fn`` returns are valid, and
    not written, until the call AFTER the next returns: a trainer may
    ask for round n+1 while round n still trains from its buffer.

    An error the data fn raises on the thread surfaces from ``fn``.
    ``fn.close()`` stops and joins the thread; a feed nobody closes
    cannot hold the process (a daemon).  The one round read past the
    last one asked for is the price."""
    bufs = [RoundBuffer(train_fn, tau, num_workers) for _ in range(3)]
    asked: queue.SimpleQueue = queue.SimpleQueue()  # ``it`` of a round to fill
    feed = None  # the thread, from the first call on

    def fill(thread):
        index = 0
        for buf in itertools.cycle(bufs):
            it = asked.get()
            if it is None:  # close()
                return
            for t in range(tau):
                for w in range(num_workers):
                    if thread.stopped:
                        return
                    buf.read(index, t, w)
                    if index == 0:  # the shapes are known: the other two
                        for other in bufs[1:]:
                            other.make_like(buf)
                    index += 1
                buf.slot(it, t)
            if not thread.put(dict(buf.arrays), it):
                return

    def fn(it):
        nonlocal feed
        if feed is None:
            feed = FeedThread(fill, depth=1)
            asked.put(it)
        arrays = feed.get(it)
        if arrays is DONE:
            raise RuntimeError("the tau-round feed was closed")
        asked.put(it + tau)  # the trainer's next ``it``; names spans only
        return arrays

    def close():
        if feed is not None:
            asked.put(None)
            feed.close()

    fn.close = close
    return fn


def widen_batch(train_fn, num_workers, keep=1):
    """tau=1 global batch ``[B*workers, ...]``: one per-worker batch per
    worker, side by side in a slot of a ``RoundBuffer``.  A batch is
    valid until ``keep`` calls later (the host-buffer rule, module
    docstring)."""
    if num_workers == 1:
        return train_fn
    buf = RoundBuffer(train_fn, keep, num_workers)
    calls = [0]

    def fn(it):
        t = calls[0] % keep
        calls[0] += 1
        for w in range(num_workers):
            buf.read(it * num_workers + w, t, w)
        return buf.slot(it, t)

    return fn
