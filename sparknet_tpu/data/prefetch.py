"""Background device prefetcher: the async host→HBM feed.

Equivalent of Caffe's prefetch pipeline (ref:
caffe/src/caffe/layers/base_data_layer.cpp:70-118 +
caffe/include/caffe/data_layers.hpp:85-93: ``PREFETCH_COUNT = 3`` batch
slots cycling through free/full BlockingQueues, with the prefetch thread
also performing the host→GPU copy).  Here the worker thread runs the host
transform AND ``jax.device_put`` so transfer overlaps the previous step's
compute; the consumer pops device-resident arrays.  Queue depth defaults
to the reference's 3.

A data fn that takes a destination (``takes_out``: ``data_fn(it, out=)``,
the ``db:`` cursor) is handed one of ``RING`` host batches the prefetcher
owns and refills, so the feed allocates nothing batch-sized per step.
When a slot may be refilled is the host-buffer rule (``data/rounds.py``);
with two slots the wait falls a whole read after the put and costs
nothing.

The reference's ``InternalThread`` clones RNG/mode state into the child
(ref: caffe/src/caffe/util/internal_thread.cpp:28-49); here the data_fn
closure owns its own seeded numpy RandomState, so the thread needs no
global state cloning.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable

import jax
import numpy as np

from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import feed_counts

PREFETCH_COUNT = 3
RING = 2  # host batches a takes_out data fn is handed in turn


def fresh_bytes(feeds: dict, out: dict | None) -> int:
    """Bytes of the host batch ``feeds`` that lie outside the destination
    ``out`` it was read with: what the read had to allocate."""
    return sum(
        int(getattr(v, "nbytes", 0)) for k, v in feeds.items()
        if out is None or not np.may_share_memory(v, out[k]))


def _empty_like(feeds: dict) -> dict:
    return {k: np.empty_like(v) for k, v in feeds.items()}


def _aliases_host(placed) -> bool:
    """True where a ``device_put`` may hand back the host array's own
    memory: the CPU backend, told by the placed arrays' platform."""
    return any(d.platform == "cpu"
               for x in jax.tree_util.tree_leaves(placed)
               for d in x.devices())


def _transferred(placed) -> bool:
    """Wait until the arrays placed from a slot, a whole read ago, are
    ready.  False where the consumer deleted one meanwhile: its transfer
    cannot be awaited, so the slot's memory is left to it."""
    try:
        jax.block_until_ready(placed)
    except RuntimeError:
        return False
    return True


class DevicePrefetcher:
    """Wraps ``data_fn(it) -> feeds`` into an iterator of device-placed
    feeds, produced ahead of consumption by a daemon thread."""

    def __init__(
        self,
        data_fn: Callable[[int], dict[str, Any]],
        num_iters: int,
        sharding=None,
        depth: int = PREFETCH_COUNT,
        start_iter: int = 0,
        device_fn: Callable[[dict[str, Any], int], dict[str, Any]] | None = None,
    ):
        """``device_fn(feeds, it)`` post-processes device-resident feeds —
        e.g. :class:`~sparknet_tpu.data.device_transform.DeviceAugment`
        so the host ships uint8 and the crop/mirror/mean run in XLA.  The
        worker thread only *dispatches* it (async), so it overlaps the
        previous step's compute like the transfer does."""
        self._data_fn = data_fn
        self._num = num_iters
        self._sharding = sharding
        self._device_fn = device_fn
        self._start = start_iter
        self._feed = FeedThread(self._worker, depth)

    def _worker(self, feed: FeedThread) -> None:
        # the feed thread's spans (sn.feed.put / augment / full; the data
        # fn brings its own sn.feed.read) time the HOST side of each
        # stage: the transfer and the augment are dispatched, not awaited
        #
        # the ring: host batches and what was last placed from each.
        # None until the first batch (read into a fresh array) has told
        # the shapes and where device_put lands; stays None for a data
        # fn without ``takes_out`` and on an aliasing backend
        ring: list[dict] | None = None
        placed: list = [None] * RING
        for it in range(self._start, self._start + self._num):
            if feed.stopped:
                return
            slot = (it - self._start) % RING
            if ring is None:
                host = self._data_fn(it)
            else:
                if not _transferred(placed[slot]):
                    ring[slot] = _empty_like(ring[slot])
                placed[slot] = None
                host = self._data_fn(it, out=ring[slot])
            with get_recorder().span("sn.feed.put", host=True, it=it,
                                     **feed_counts(host)):
                if self._sharding is not None:
                    feeds = {
                        k: jax.device_put(v, self._sharding)
                        for k, v in host.items()
                    }
                else:
                    feeds = jax.device_put(host)
            if ring is not None:
                placed[slot] = feeds
            elif (it == self._start
                    and getattr(self._data_fn, "takes_out", False)
                    and not _aliases_host(feeds)):
                ring = [_empty_like(host) for _ in range(RING)]
            del host
            if self._device_fn is not None:
                # ``fused``: 1 where the batch takes the augment's one
                # pass, 0 where it falls back (DeviceAugment.device_fn
                # says; another device_fn carries no such stat)
                fused = getattr(self._device_fn, "fused", None)
                stat = {} if fused is None else {"fused": fused(feeds)}
                with get_recorder().span("sn.feed.augment", host=True,
                                         compile_stats=True, it=it, **stat):
                    feeds = self._device_fn(feeds, it)
            if not feed.put(feeds, it):
                return

    def close(self) -> None:
        """Stop the worker and release queued device batches."""
        self._feed.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __iter__(self):
        it = self._start
        while True:
            item = self._feed.get(it)
            if item is DONE:
                return
            it += 1
            yield item

    def __len__(self) -> int:
        return self._num


class _Done:
    pass


DONE = _Done()  # what FeedThread.get gives once the stream has ended


class FeedThread:
    """A daemon thread that produces ahead of one consumer, and what the
    two share: the bounded queue between them, the stop, the way an error
    takes to the consumer's side, and the end.  ``produce(feed)`` runs on
    the thread and hands over each item with ``feed.put(item, it)``; it
    returns when it has no more, or when ``put`` says the feed was
    closed.  ``DevicePrefetcher`` produces placed batches this way,
    ``rounds.stack_tau`` whole tau-rounds."""

    def __init__(self, produce: Callable[["FeedThread"], None], depth: int):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._ended = False
        self.thread = threading.Thread(target=self._run, args=(produce,),
                                       daemon=True)
        self.thread.start()

    def _run(self, produce) -> None:
        try:
            produce(self)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        self.put(DONE)

    @property
    def stopped(self) -> bool:
        return self._stop.is_set()

    def put(self, item, it: int = -1) -> bool:
        """Bounded put that aborts on close() so an abandoned consumer
        doesn't leave the thread pinning what it produced forever.  The
        time it is blocked on a full queue (the feed is ahead of the
        device) is the ``sn.feed.full`` span."""
        if self._stop.is_set():
            return False
        try:
            self._q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with get_recorder().span("sn.feed.full", host=True, it=it):
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
        return False

    def get(self, it: int):
        """The next item, waited for under an ``sn.feed.wait`` span whose
        ``ready`` stat is 1 when the item was there before it was asked
        for, else 0.  ``DONE`` once the thread has ended or the feed was
        closed: a single-use stream, so asking again never blocks on the
        empty queue.  What ended the thread is raised here, every time."""
        if not self._ended:
            with get_recorder().span("sn.feed.wait", host=True, it=it,
                                     ready=int(not self._q.empty())):
                item = self._q.get()
            if item is not DONE:
                return item
            self._ended = True
        if self._err is not None:
            raise self._err
        return DONE

    def close(self) -> None:
        """Stop the thread and release what it had queued."""
        self._stop.set()
        self._drain()
        self.thread.join(timeout=5.0)
        self._drain()  # a racing put may have landed one item mid-drain
        self._ended = True

    def _drain(self) -> None:
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
