"""Overlapped training feed path: device prefetch + async loss readback.

A synchronous fit loop leaves the device idle on every batch: host-side
ETL + ``jnp.asarray`` (and, sharded, the blocking ``shard_batch`` transfer)
run *between* steps, and listener delivery — which may read ``float(loss)``
and therefore sync on the device — runs *before* the next batch is even
fetched. ``AsyncDataSetIterator`` only overlaps host ETL; the host→device
leg and the loss readback stay on the critical path.

This module is the training-side analog of the serving pipeline
(``serving/batcher.py``, ISSUE 3): the feed path becomes explicit stages
that overlap with device execution, while the dispatch *order* — and with
it the rng-key sequence and the whole trajectory — stays exactly the
synchronous loop's, so results are bit-identical.

- :class:`DevicePrefetcher` — background stage that pulls from any
  ``DataSetIterator`` (composing with ``AsyncDataSetIterator`` for ETL),
  coerces the batch (``coerce_training_batch``) and issues the host→device
  transfer ahead of time, keeping up to ``prefetch_buffer`` batches staged
  while the current step executes. Bounded-queue backpressure; a
  ``train.prefetch.fetch`` chaos point per fetch; a worker fault surfaces
  on the consumer's next pull and ``close()`` never leaves a live thread.
- :class:`AsyncLossDelivery` — completion stage: listener delivery
  (``iteration_done``, ``PerformanceListener.record_batch``) moves to a
  single worker that preserves submission order and exact callback
  arguments but no longer blocks dispatch when a listener reads the score.
  Mirrors ``GroupedDispatch``'s snapshot-before-deliver discipline: items
  are snapshotted at submit, delivered FIFO, drained on every exit path.
- :func:`coerce_training_batch` — ``MultiLayerNetwork``'s batch coercion
  and mask defaulting.

Only listeners that declare ``needs_model_state = False`` may be delivered
asynchronously: a state-reading listener must observe the post-step
``train_state`` of *its* iteration, which forces one-at-a-time dispatch
(the same gate ``train.fit_engine.PackedStepLoop`` applies to state
packing and grouping).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.runtime import chaos

#: Queue tokens. ``_DONE`` ends a stream; never user data.
_DONE = object()
_STOP = object()

#: Chaos point fired once per fetched batch on the training feed path,
#: before coercion/transfer — in the prefetch worker when prefetching,
#: inline on the synchronous path, so one drill schedule covers both.
FETCH_POINT = "train.prefetch.fetch"


def stateless_listeners(model) -> bool:
    """True when every attached listener declares it never reads
    ``model.train_state`` — the gate for async loss readback (and the same
    condition state packing uses)."""
    return all(not getattr(l, "needs_model_state", True)
               for l in getattr(model, "_listeners", []))


def coerce_training_batch(model, batch):
    """Coerce a ``DataSet`` minibatch to step arguments ``(x, y, fm, lm)``.

    The labels mask defaults to the features mask propagated through any
    time-axis-changing layers (``model._output_time_mask``) for
    per-timestep labels — the reference's tBPTT/masking semantics.
    ``MultiLayerNetwork._prepare_batch``; pure host→device work, safe
    off-thread.
    """
    x = jnp.asarray(batch.features)
    y = jnp.asarray(batch.labels)
    fm = None if batch.features_mask is None else jnp.asarray(batch.features_mask)
    lm = jnp.asarray(batch.labels_mask) if batch.labels_mask is not None \
        else (model._output_time_mask(fm) if y.ndim == 3 else None)
    return x, y, fm, lm


class _SyncBatchSource:
    """Degenerate source: fetch+coerce inline on the consumer thread —
    byte-for-byte the old synchronous loop, plus data-wait timing."""

    def __init__(self, iterator, prepare, profiler=None):
        self._iterator = iterator
        self._prepare = prepare
        self._profiler = profiler

    def __iter__(self) -> Iterator[Any]:
        # explicit reset BEFORE iterating, exactly as the old fit loops did:
        # not every iterator's __iter__ resets (the fault-tolerance fence
        # and skip wrappers iterate from their current position)
        self._iterator.reset()
        it = iter(self._iterator)
        prof = self._profiler
        while True:
            if prof is None:
                try:
                    ds = next(it)
                except StopIteration:
                    return
                chaos.inject(FETCH_POINT)
                yield self._prepare(ds)
                continue
            fetched = prof.stage("next_batch")
            try:
                with fetched:
                    ds = next(it)
                    chaos.inject(FETCH_POINT)
            except StopIteration:
                # the probe that found the end: waited for, not a batch
                prof.record_data_wait(fetched.seconds, counted=False)
                return
            with prof.stage("h2d") as copied:
                item = self._prepare(ds)
            prof.record_data_wait(fetched.seconds + copied.seconds)
            yield item

    def close(self) -> None:
        pass


class DevicePrefetcher:
    """Background fetch/coerce/transfer stage over a ``DataSetIterator``.

    The worker thread iterates the base iterator (through the normal
    ``__iter__`` protocol, so ``reset()`` and ``pre_processor`` semantics
    are preserved), fires the ``train.prefetch.fetch`` chaos point, runs
    ``prepare(ds)`` — batch coercion plus the ahead-of-time
    ``jax.device_put`` (sharded via the strategy's ``NamedSharding``s under
    ``ParallelWrapper``) — and stages the result in a bounded queue of
    ``buffer`` batches. The consumer iterates in FIFO order, so the step
    sequence is exactly the synchronous loop's.

    A worker fault (iterator error, failed transfer, injected chaos)
    surfaces on the consumer's **next** pull — staged batches after the
    fault are discarded — and the worker exits. ``close()`` (every exit
    path must call it) stops the worker promptly even when it is blocked on
    a full queue, and closes the underlying iterator's own worker when it
    has one (``AsyncDataSetIterator.close``), so no thread outlives the
    fit that started it.
    """

    def __init__(self, iterator, prepare: Callable[[Any], Any],
                 buffer: int = 2, profiler=None, name: str = "train-prefetch"):
        self._iterator = iterator
        self._prepare = prepare
        self._profiler = profiler
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, int(buffer)))
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, name=name,
                                        daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- worker
    def _worker(self) -> None:
        from deeplearning4j_tpu.data.iterators import stop_aware_put
        try:
            # explicit reset first (see _SyncBatchSource.__iter__): wrappers
            # like the fault-tolerance skip iterator only rewind on reset()
            self._iterator.reset()
            for ds in self._iterator:
                if self._stop.is_set():
                    return
                chaos.inject(FETCH_POINT)
                if self._profiler is None:
                    item = self._prepare(ds)
                else:  # off the fit thread: in h2d's total, not in data_wait
                    with self._profiler.stage("h2d"):
                        item = self._prepare(ds)
                if not stop_aware_put(self._queue, item, self._stop):
                    return
        except BaseException as e:  # surfaced on the consumer side
            self._error = e
        finally:
            stop_aware_put(self._queue, _DONE, self._stop)

    # ----------------------------------------------------------- consumer
    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def __iter__(self) -> Iterator[Any]:
        while True:
            # a fault that already happened surfaces NOW, before any batch
            # staged behind it — the fit fails at the fault, not after
            # training the tail of the buffer
            self._raise_pending()
            if self._profiler is None:
                item = self._queue.get()
            else:
                with self._profiler.stage("next_batch") as waited:
                    item = self._queue.get()
                self._profiler.record_data_wait(waited.seconds)
            if item is _DONE:
                self._raise_pending()
                return
            yield item

    def close(self) -> None:
        """Stop the worker and join it; idempotent, called on every fit
        exit path (epoch end, fault, KeyboardInterrupt)."""
        from deeplearning4j_tpu.data.iterators import drain_and_join
        self._stop.set()
        drain_and_join(self._queue, self._thread)
        # a mid-stream close leaves a composed AsyncDataSetIterator's own
        # worker parked on ITS queue; shut it down too (reset() restarts it)
        closer = getattr(self._iterator, "close", None)
        if callable(closer):
            closer()


def batch_source(iterator, prepare, prefetch_buffer: int = 0, profiler=None,
                 name: str = "train-prefetch"):
    """The fit loops' one switch between the synchronous feed path and the
    staged pipeline: ``prefetch_buffer == 0`` fetches inline (bit-for-bit
    the old loop), ``> 0`` stages that many batches ahead."""
    if prefetch_buffer and int(prefetch_buffer) > 0:
        return DevicePrefetcher(iterator, prepare, buffer=int(prefetch_buffer),
                                profiler=profiler, name=name)
    return _SyncBatchSource(iterator, prepare, profiler=profiler)


class AsyncLossDelivery:
    """Completion-path listener delivery (single worker, FIFO).

    ``submit(args, loss)`` snapshots the step's bookkeeping arguments and
    returns immediately; the worker calls ``deliver(args, loss)`` — the fit
    loop's existing score/iteration/listener bookkeeping — in submission
    order. A listener that reads ``float(loss)`` now syncs on the worker,
    not on the dispatch loop, so the next step is already in flight while
    the previous loss is read back.

    Submit only what deliver reads (the fit loops pass the batch SIZE, not
    the batch): queued items pin their payload for up to ``max_pending``
    deliveries, and holding full device batches there would retain memory
    the synchronous loop released after one step.

    Exact-semantics contract: same callbacks, same arguments, same order as
    the synchronous loop; only the thread (and hence *when* a listener
    exception surfaces) differs. A listener exception is recorded, later
    deliveries are skipped, and the error re-raises on the next
    ``submit``/``flush``/``raise_pending`` — ``fit`` drains on every exit
    path, so it never passes silently.
    """

    def __init__(self, deliver: Callable[[Any, Any], None], max_pending: int = 64,
                 profiler=None, name: str = "train-listener-delivery"):
        self._deliver = deliver
        self._profiler = profiler
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, int(max_pending)))
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread = threading.Thread(target=self._worker, name=name,
                                        daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        while True:
            item = self._queue.get()
            try:
                if item is _STOP:
                    return
                args, loss, t0 = item
                if self._error is not None:
                    continue  # keep draining so submit() can't deadlock
                try:
                    if self._profiler is not None:
                        with self._profiler.stage("step", started=t0):
                            jax.block_until_ready(loss)
                    self._deliver(args, loss)
                except BaseException as e:
                    self._error = e
            finally:
                self._queue.task_done()

    def submit(self, args, loss) -> None:
        self.raise_pending()
        self._queue.put((args, loss, time.perf_counter()))

    def flush(self) -> None:
        """Barrier: every submitted delivery has run (epoch boundaries —
        ``on_epoch_end`` must observe all of its epoch's iterations)."""
        self._queue.join()
        self.raise_pending()

    def raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def shutdown(self) -> None:
        """Drain remaining deliveries and stop the worker; never raises
        (exceptional exits must not mask the original error — the happy
        path calls :meth:`raise_pending` afterwards). Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._queue.put(_STOP)
        self._thread.join()
