"""Production input pipeline: multi-worker host feed + device prefetch.

DESIGN.md §15. Three stages, each independently bounded:

1. **Host producers** — ``num_workers`` threads claim step numbers from a
   shared counter and call ``source.batch_at(step)`` concurrently.
   Because every sample is counter-keyed by ``(seed, split, step,
   global_index)`` (synthetic.py), steps are embarrassingly parallel and
   ordering is purely a delivery concern.
2. **Ordered reorder buffer** — completed batches park in a dict keyed
   by step; the consumer takes them strictly in step order. Backpressure
   bounds the claim horizon to ``depth`` steps past the last delivered
   one, so a stuck consumer stalls producers instead of buffering
   unboundedly.
3. **Device double-buffer** — when a ``put`` callable is given
   (``jax.device_put`` with the step's input sharding), the *next*
   step's host batch is staged onto device while the caller consumes the
   current one, overlapping H2D transfer with compute. JAX dispatch is
   async, so ``put`` returns immediately and the transfer proceeds in
   the background.

Error contract (ported from the legacy ``Prefetcher``): a worker
exception is tagged with its step and delivered from ``next()`` when the
consumer *reaches* that step — batches for earlier steps still arrive,
later claims are cancelled. The exception is raised exactly once;
subsequent ``next()`` calls raise ``StopIteration`` (re-raising one
exception object repeatedly accumulates traceback frames). ``close()``
is race-free against concurrently blocked consumers and producers: both
wait on the same condition variable and re-check the closed flag.

Boundedness attribution (§15): the pipeline records three spans
(``repro.telemetry``), each carrying the step it serves: ``feed.wait``,
the consumer blocked in ``next()`` until the step's batch is in hand (a
cold or late step's ``put`` included, the stage-ahead not);
``feed.put``, each device ``put``; and ``feed.batch``, a worker's
``batch_at`` plus transform. A compute-bound run shows ~zero wait (the
buffer is always ahead); a data-starved run shows wait ≈ step-time gap.
The trainer and step_bench read the ``feed.wait`` spans.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

import jax

from repro import telemetry


class DataPipeline:
    """Multi-worker, step-ordered, optionally device-staged prefetcher.

    Drop-in for the legacy ``Prefetcher`` (same ``(step, batch)``
    iteration and error/close contract) with ``num_workers`` host
    producer threads and an optional device stage.

    Args:
      source: object with ``batch_at(step) -> pytree of np.ndarray``.
      start_step: first step to produce.
      depth: reorder-buffer bound — producers may run at most ``depth``
        steps ahead of the consumer.
      transform: host-side callable applied by the producing worker
        (e.g. augmentation); runs concurrently across workers.
      num_workers: producer thread count (>= 1).
      put: optional device-staging callable (``jax.device_put`` bound to
        the input sharding); applied on the consumer thread one step
        ahead of delivery so transfer overlaps the caller's compute.
      device_ahead: how many steps to stage through ``put`` beyond the
        one being returned (0 disables staging even if ``put`` is set).
    """

    def __init__(self, source, start_step: int = 0, depth: int = 4,
                 transform: Optional[Callable[[Any], Any]] = None,
                 *, num_workers: int = 1,
                 put: Optional[Callable[[Any], Any]] = None,
                 device_ahead: int = 1):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        self.source = source
        self.transform = transform
        self.num_workers = num_workers
        self._put = put
        self._device_ahead = max(0, device_ahead) if put is not None else 0
        self._depth = depth
        self._cv = threading.Condition()
        self._ready: Dict[int, Any] = {}      # step -> host batch
        self._next_claim = start_step         # next step a worker takes
        self._next_out = start_step           # next step the consumer needs
        self._closed = False
        self._error: Optional[BaseException] = None
        self._error_step: Optional[int] = None
        self._raised = False
        # device stage: (step, staged batch) in step order
        self._staged: deque = deque()
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"data-worker-{i}")
            for i in range(num_workers)
        ]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------- workers

    def _worker(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self._closed or self._error is not None:
                        return
                    if self._next_claim < self._next_out + self._depth:
                        step = self._next_claim
                        self._next_claim += 1
                        break
                    self._cv.wait(timeout=0.1)
            try:
                with telemetry.span("feed.batch", step):
                    batch = self.source.batch_at(step)
                    if self.transform is not None:
                        batch = self.transform(batch)
            except BaseException as e:
                with self._cv:
                    # keep the error of the smallest step: it is the one
                    # the consumer will hit first, and later steps may
                    # only have failed as a consequence of it
                    if (self._error is None
                            or step < self._error_step):  # type: ignore
                        self._error = e
                        self._error_step = step
                    self._cv.notify_all()
                return
            with self._cv:
                if self._closed:
                    return
                self._ready[step] = batch
                self._cv.notify_all()

    # ------------------------------------------------------------ consumer

    def _host_get(self, step: int, block: bool):
        """Take ``step``'s host batch from the reorder buffer.

        Raises the worker error only when the consumer has *reached* the
        failed step. Non-blocking mode returns None when not ready and
        never raises — used for opportunistic device staging, where a
        pending error must stay attributed to its own step."""
        with self._cv:
            while True:
                if step in self._ready:
                    batch = self._ready.pop(step)
                    self._cv.notify_all()  # frees a claim slot
                    return batch
                if not block:
                    return None
                if self._error is not None and self._error_step <= step:
                    if self._raised:
                        raise StopIteration
                    self._raised = True
                    raise self._error
                if self._closed:
                    raise StopIteration
                self._cv.wait(timeout=0.1)

    def _stage_through(self, step: int) -> None:
        """Opportunistically push host batches for steps up to and
        including ``step`` through the device stage (non-blocking)."""
        while self._staged and self._staged[0][0] < self._next_out:
            self._staged.popleft()  # dropped by a restart seek; unreachable
        last = self._staged[-1][0] if self._staged else self._next_out - 1
        while last < step:
            nxt = last + 1
            host = self._host_get(nxt, block=False)
            if host is None:
                return
            self._staged.append((nxt, self._staged_put(nxt, host)))
            last = nxt

    def _staged_put(self, step: int, host):
        with telemetry.span("feed.put", step):
            return self._put(host)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        step = self._next_out
        with telemetry.span("feed.wait", step):
            if self._put is not None:
                if not (self._staged and self._staged[0][0] == step):
                    # cold start / staging fell behind: block for it
                    host = self._host_get(step, block=True)
                    self._staged.append((step,
                                         self._staged_put(step, host)))
                _, batch = self._staged.popleft()
            else:
                batch = self._host_get(step, block=True)
        self._next_out = step + 1
        with self._cv:
            self._cv.notify_all()
        if self._put is not None:
            # stage ahead for future steps while compute runs
            self._stage_through(step + self._device_ahead)
        return step, batch

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=2)
        with self._cv:
            self._ready.clear()
            self._staged.clear()


class AugmentedSource:
    """Host-path reference augmentation (numpy mirror of the fused
    kernel, DESIGN.md §15): per-sample horizontal flip + cyclic
    translation (crop proxy) + per-channel normalize, with parameters
    drawn from the *same* ``jax.random`` stream as the on-device path
    (``ops.input_augment_params``), so host-path and fused-input runs
    consume identical augmented pixels up to dtype rounding.

    ``train=False`` applies normalization only (the eval variant)."""

    def __init__(self, source, seed: int, mean, std, max_shift: int = 4,
                 train: bool = True, global_batch: Optional[int] = None):
        self.source = source
        self.seed = seed
        self.mean = np.asarray(mean, np.float32).reshape(1, 1, 1, -1)
        self.inv_std = (1.0 /
                        np.asarray(std, np.float32)).reshape(1, 1, 1, -1)
        self.max_shift = max_shift
        self.train = train
        # shard bookkeeping for parameter slicing: params are always
        # drawn at the *global* batch size and sliced, because threefry
        # draws are not prefix-stable across different draw sizes — all
        # hosts (and the on-device kernel path) must use the same total
        self.sample_offset = getattr(source, "sample_offset", 0)
        self.global_batch = (global_batch if global_batch is not None
                             else self.sample_offset + source.batch)

    @property
    def batch(self) -> int:
        return self.source.batch

    def batch_at(self, step: int) -> Dict[str, Any]:
        batch = dict(self.source.batch_at(step))
        x = batch["images"].astype(np.float32, copy=True)
        if self.train:
            from repro.kernels import ops  # lazy: keeps data/ jax-light
            b = x.shape[0]
            params = np.asarray(ops.input_augment_params(
                self.seed, step, self.global_batch,
                max_shift=self.max_shift))
            params = params[self.sample_offset:self.sample_offset + b]
            for j in range(b):
                flip, dy, dx, _ = (int(v) for v in params[j])
                img = x[j]
                if flip:
                    img = img[:, ::-1, :]
                img = np.roll(img, (dy, dx), axis=(0, 1))
                x[j] = img
        x = (x - self.mean) * self.inv_std
        batch["images"] = x
        return batch


class StepStampSource:
    """Wraps a source so each batch carries its step number as an
    ``input_step`` scalar — the seed material the fused input kernel
    needs to derive per-step augmentation parameters on device
    (DESIGN.md §15). The scalar rides the batch pytree so donation,
    prefetch and restart logic need no side-channel."""

    def __init__(self, source):
        self.source = source
        self.sample_offset = getattr(source, "sample_offset", 0)

    @property
    def batch(self) -> int:
        return self.source.batch

    def batch_at(self, step: int) -> Dict[str, Any]:
        batch = dict(self.source.batch_at(step))
        batch["input_step"] = np.int32(step)
        return batch
