"""Core iterator interfaces + NDArrayIter (reference ``python/mxnet/io/io.py``)."""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, namedtuple

import numpy as np

from .. import ndarray as nd
from ..ndarray import NDArray
from ..resilience import faults as _faults
from ..telemetry import bus as _tel

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "DevicePrefetchIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name/shape/type descriptor (reference ``io.py:68``)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    @staticmethod
    def get_batch_axis(layout):
        """Axis of the batch dimension in ``layout`` (reference ``io.py:118``)."""
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    """One mini-batch (reference ``io.py:146``)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None:
            assert isinstance(data, (list, tuple)), "Data must be list of NDArrays"
        if label is not None:
            assert isinstance(label, (list, tuple)), "Label must be list of NDArrays"
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data]
        if self.label:
            label_shapes = [l.shape for l in self.label]
        else:
            label_shapes = None
        return "{}: data shapes: {} label shapes: {}".format(
            self.__class__.__name__, data_shapes, label_shapes)


class DataIter:
    """Iterator base (reference ``io.py:212``)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class ResizeIter(DataIter):
    """Resize an iterator to ``size`` batches per epoch (reference
    ``io.py:308``)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Thread-backed prefetcher over one or more iterators (reference
    ``io.py:381``; the dmlc ThreadedIter double-buffering role)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0].shape[0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]
        # a worker exception parks here (never swallowed): iter_next
        # re-raises it on the consumer thread with the original traceback
        self.worker_exc = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                # producer wait: the decode thread blocked on the consumer
                # taking the previous batch — device-bound when large.
                # Counted only when a batch follows: the shutdown wake-up
                # is not a stall (same rule as DevicePrefetchIter).
                t0 = time.perf_counter()
                self.data_taken[i].wait()
                if not self.started:
                    break
                if _tel.enabled:
                    _tel.count("io.producer_wait_ms",
                               (time.perf_counter() - t0) * 1e3)
                try:
                    if _faults.active:
                        _faults.check("io.prefetch")
                    with _tel.span("io.produce_batch", iter=i):
                        self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                except BaseException as e:
                    # a raising worker used to die silently, stranding the
                    # consumer on data_ready forever; park the exception
                    # for the consumer and stop this worker (the iterator
                    # is broken — reset() restarts nothing here)
                    self.worker_exc[i] = e
                    self.next_batch[i] = None
                    if _tel.enabled:
                        _tel.count("io.worker_error", stage="prefetch")
                        _tel.instant("io.worker_error", stage="prefetch",
                                     iter=i, error=repr(e))
                    self.data_taken[i].clear()
                    self.data_ready[i].set()
                    return
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for thread in self.prefetch_threads:
            thread.start()

    def __del__(self):
        self.started = False
        for e in self.data_taken:
            e.set()
        for thread in self.prefetch_threads:
            thread.join(timeout=1.0)

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        # bounded like iter_next: resetting a pipeline whose worker died
        # (sticky parked exception, thread exited) must raise, not hang
        # forever on a data_ready event nothing will ever set again
        for i, e in enumerate(self.data_ready):
            while self.worker_exc[i] is None and not e.wait(timeout=1.0):
                if not self.prefetch_threads[i].is_alive():
                    raise RuntimeError(
                        f"PrefetchingIter worker {i} died without "
                        "producing a batch or an exception")
            if self.worker_exc[i] is not None:
                raise self.worker_exc[i]
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        # consumer wait: the training loop blocked on decode — host-bound
        # when large (the "host-staging-bound" diagnosis as a first-class
        # number).  Bounded waits: a prefetch worker that died
        # without parking an exception (killed interpreter-side) must not
        # hang the training loop forever.
        t0 = time.perf_counter()
        for i, e in enumerate(self.data_ready):
            while not e.wait(timeout=1.0):
                if self.worker_exc[i] is not None:
                    raise self.worker_exc[i]
                if not self.prefetch_threads[i].is_alive():
                    raise RuntimeError(
                        f"PrefetchingIter worker {i} died without "
                        "producing a batch or an exception")
        for i, exc in enumerate(self.worker_exc):
            if exc is not None:
                # re-raise on the consumer thread; the exception object
                # still carries the worker's original traceback.  STICKY:
                # the worker is dead and next_batch may hold a mix of
                # parked batches and Nones, so a later call must keep
                # raising rather than misreport a clean epoch end (or
                # trip over a None batch) after the caller swallowed the
                # first raise
                raise exc
        if self.next_batch[0] is None:
            # epoch-end sentinel: discovering StopIteration is not a
            # pipeline stall (same rule as DevicePrefetchIter)
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iterators"
            return False
        if _tel.enabled:
            _tel.count("io.consumer_wait_ms",
                       (time.perf_counter() - t0) * 1e3)
            _tel.count("io.batches")
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Different pad size between iterators"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], [])
            if self.next_batch[0].label is not None else None,
            self.next_batch[0].pad,
            self.next_batch[0].index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _init_data(data, allow_empty, default_name):
    """Normalize data into an OrderedDict of name→np.ndarray (reference
    ``io.py:574 _init_data``)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = OrderedDict([(default_name, data[0])])
        else:
            data = OrderedDict(
                [("_%d_%s" % (i, default_name), d) for i, d in enumerate(data)])
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    out = OrderedDict()
    for k, v in data.items():
        if isinstance(v, NDArray):
            out[k] = v.asnumpy()
        else:
            out[k] = np.asarray(v)
    return list(out.items())


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference ``io.py:623``): shuffle,
    ``last_batch_handle`` ∈ {'pad', 'discard', 'roll_over'}."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.idx = np.arange(self.data[0][1].shape[0])
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.batch_size = batch_size
        self.cursor = -self.batch_size
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        if self.shuffle:
            self._shuffle_data()
        self.cursor = -self.batch_size

    def reset(self):
        if self.shuffle:
            self._shuffle_data()
        if (self.last_batch_handle == "roll_over"
                and 0 < self.cursor < self.num_data):
            self.cursor = self.cursor - self.num_data - self.batch_size
        else:
            self.cursor = -self.batch_size

    def _shuffle_data(self):
        perm = np.random.permutation(self.num_data)
        self.idx = self.idx[perm] if self.idx is not None else perm
        self.data = [(k, v[perm]) for k, v in self.data]
        self.label = [(k, v[perm]) for k, v in self.label]
        self.idx = np.arange(self.num_data)

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if not self.iter_next():
            raise StopIteration
        if self.last_batch_handle == "discard" and \
                self.cursor + self.batch_size > self.num_data:
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=None)

    def _batchify(self, data_source):
        """Slice [cursor, cursor+batch) with pad wraparound (reference
        ``io.py:783 _getdata``)."""
        assert self.cursor < self.num_data
        start = max(self.cursor, 0)
        end = self.cursor + self.batch_size
        if end <= self.num_data:
            return [nd.array(v[start:end]) for _, v in data_source]
        # pad: wrap from the beginning (last_batch_handle='pad')
        pad = end - self.num_data
        return [nd.array(np.concatenate([v[start:], v[:pad]], axis=0))
                for _, v in data_source]

    def getdata(self):
        return self._batchify(self.data)

    def getlabel(self):
        return self._batchify(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        if self.last_batch_handle == "roll_over" and self.cursor < 0:
            return -self.cursor
        return 0


class DevicePrefetchIter:
    """Double-buffered host→device staging (the ``iter_prefetcher.h`` role
    extended across the host-to-device hop): a background thread pulls host
    batches from ``data_iter`` and issues ``stage_fn`` (typically
    ``jax.device_put`` onto the training sharding) one-ahead, so batch
    N+1 transfers while the device steps batch N.  Exposed IO per step
    drops from (stage + step) to max(0, stage − step).

    ``stage_fn(batch) -> payload`` runs ON THE PREFETCH THREAD; the
    iterator yields the staged payloads in order.  ``depth`` bounds the
    number of in-flight staged batches (2 = classic double buffer).
    """

    _END = object()

    def __init__(self, data_iter, stage_fn, depth=2):
        import queue
        self._it = data_iter
        self._stage = stage_fn
        self._q = queue.Queue(maxsize=max(1, int(depth)))
        self._thread = None
        self._stop = False
        self._done = False        # epoch ended (or errored): next raises

    def _worker(self):
        try:
            for batch in self._it:
                if self._stop:
                    return
                if _faults.active:
                    _faults.check("io.prefetch")
                with _tel.span("io.stage_batch"):
                    staged = self._stage(batch)
                t0 = time.perf_counter()
                self._q.put(staged)
                if _tel.enabled:
                    # blocked on a full queue: the device is the slow side
                    _tel.count("io.producer_wait_ms",
                               (time.perf_counter() - t0) * 1e3)
                if self._stop:
                    return
            self._q.put(self._END)
        except BaseException as e:          # surfaced on the consumer side
            if _tel.enabled:
                _tel.count("io.worker_error", stage="stage")
                _tel.instant("io.worker_error", stage="stage",
                             error=repr(e))
            self._q.put(e)

    def __iter__(self):
        self.reset()
        return self

    def reset(self):
        old = self._thread
        if old is not None and old.is_alive():
            self._stop = True
            try:
                while True:
                    self._q.get_nowait()
            except Exception:
                pass
            old.join(timeout=30.0)
            if old.is_alive():
                # refuse to start a second reader over the same iterator
                raise RuntimeError(
                    "DevicePrefetchIter.reset: the staging thread is "
                    "still inside stage_fn after 30s; cannot safely "
                    "reset the underlying iterator")
        self._stop = False
        self._done = False
        while not self._q.empty():
            self._q.get_nowait()
        self._it.reset()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def __next__(self):
        if self._thread is None:
            self.reset()
        if self._done:
            raise StopIteration
        import queue as _queue
        t0 = time.perf_counter()
        while True:
            # bounded gets: a staging thread that died without queueing its
            # exception (interpreter teardown, killed thread) must surface
            # as an error here, not hang the training loop forever
            try:
                item = self._q.get(timeout=1.0)
                break
            except _queue.Empty:
                if not self._thread.is_alive():
                    # one last non-blocking look: the thread may have
                    # queued its final item right as the timeout landed
                    try:
                        item = self._q.get_nowait()
                        break
                    except _queue.Empty:
                        self._done = True
                        raise RuntimeError(
                            "DevicePrefetchIter staging thread died "
                            "without a result") from None
        if item is self._END:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        if _tel.enabled:
            # blocked on an empty queue: staging/decode is the slow side.
            # Counted only for real batches — the end-of-epoch sentinel
            # drain is not a pipeline stall.
            _tel.count("io.consumer_wait_ms",
                       (time.perf_counter() - t0) * 1e3)
            _tel.count("io.batches")
        return item

    next = __next__
