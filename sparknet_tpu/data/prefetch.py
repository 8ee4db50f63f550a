"""Background device prefetcher: the async host→HBM feed.

Equivalent of Caffe's prefetch pipeline (ref:
caffe/src/caffe/layers/base_data_layer.cpp:70-118 +
caffe/include/caffe/data_layers.hpp:85-93: ``PREFETCH_COUNT = 3`` batch
slots cycling through free/full BlockingQueues, with the prefetch thread
also performing the host→GPU copy).  Here the worker thread runs the host
transform AND ``jax.device_put`` so transfer overlaps the previous step's
compute; the consumer pops device-resident arrays.  Queue depth defaults
to the reference's 3.

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

from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import feed_counts

PREFETCH_COUNT = 3


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
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._start = start_iter
        self._stop = threading.Event()
        self._finished = False
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        # the feed thread's spans (sn.feed.put / augment / full; the data
        # fn brings its own sn.feed.read) time the HOST side of each
        # stage: the transfer and the augment are dispatched, not awaited
        try:
            for it in range(self._start, self._start + self._num):
                if self._stop.is_set():
                    return
                feeds = self._data_fn(it)
                with get_recorder().span("sn.feed.put", host=True, it=it,
                                         **feed_counts(feeds)):
                    if self._sharding is not None:
                        feeds = {
                            k: jax.device_put(v, self._sharding)
                            for k, v in feeds.items()
                        }
                    else:
                        feeds = jax.device_put(feeds)
                if self._device_fn is not None:
                    with get_recorder().span("sn.feed.augment", host=True,
                                             it=it):
                        feeds = self._device_fn(feeds, it)
                if not self._put(feeds, it):
                    return
            self._put(_DONE)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
            self._put(_DONE)

    def _put(self, item, it: int = -1) -> bool:
        """Bounded put that aborts on close() so an abandoned consumer
        doesn't leave the worker pinning device batches forever.  The
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

    def close(self) -> None:
        """Stop the worker and release queued device batches."""
        self._stop.set()
        self._drain()
        self._thread.join(timeout=5.0)
        self._drain()  # a racing _put may have landed one item mid-drain

    def _drain(self) -> None:
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __iter__(self):
        if self._finished:
            # single-use stream: a second iteration would block forever on
            # the empty queue
            if self._err is not None:
                raise self._err
            return
        it = self._start
        while True:
            with get_recorder().span("sn.feed.wait", host=True, it=it):
                item = self._q.get()
            it += 1
            if item is _DONE:
                self._finished = True
                if self._err is not None:
                    raise self._err
                return
            yield item

    def __len__(self) -> int:
        return self._num


class _Done:
    pass


_DONE = _Done()
