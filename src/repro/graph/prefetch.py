"""Background sampler prefetch: overlap host sampling with device compute.

The Trainer's round loop used to be strictly serial — sample on host (numpy),
copy the batch out of the sampler's scratch (``jnp.array``), dispatch, repeat
— so the device sat idle through every sampling phase and the main thread
paid a full-batch copy per round. ``PrefetchSampler`` moves sampling to a
worker thread that fills preallocated *generation* buffers (round-stacked,
ready for ``make_multi_round_fn``) while the device computes the previous
step.

Safety: on CPU JAX, ``jax.device_put``/``jnp.asarray`` zero-copy alias host
numpy buffers, so a generation may only be refilled once the computation
that read it has finished. The consumer enforces that by returning a
generation token to the worker only after blocking on an output of the step
that consumed it (``retire``). With the default two generations this is
classic double buffering: the worker samples step N+1 while the device runs
step N, and refilling a buffer waits on the completion of the step that read
it — never on the step currently in flight.

The worker owns the sampler's ``np.random.Generator`` for the lifetime of
the pipeline; each ``StepBatch`` carries the generator's bit state *after*
its rounds were drawn, so checkpointing can persist an exact resume point at
any step boundary even though the worker has sampled ahead.
"""
from __future__ import annotations

import copy
import queue
import threading
from typing import List, NamedTuple, Sequence

import jax
import numpy as np

from .. import obs
from .sampler import GlasuSampler, SampledBatch


def stack_rounds(batches: Sequence[SampledBatch]) -> SampledBatch:
    """Stack per-round batches on a new leading round axis (fresh arrays)."""
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def unstack_round(batches: SampledBatch, i: int) -> SampledBatch:
    """Round ``i``'s slice of a round-stacked batch (views)."""
    return jax.tree.map(lambda x: x[i], batches)


class StepBatch(NamedTuple):
    data: SampledBatch          # every leaf: (K, ...) view into a generation
    rounds: int                 # K
    gen: int                    # generation buffer index (retire() token)
    rng_state_after: dict       # sampler bit-generator state after this step


class _WorkerError(NamedTuple):
    exc: BaseException


_STOP = -1


class PrefetchSampler:
    """Double-buffered background sampling over a fixed step schedule.

    Usage (the Trainer's loop):

        pf = PrefetchSampler(sampler, schedule)
        try:
            for _ in schedule:
                step = pf.get()                  # blocks on the worker only
                out = backend.run_step(..., step.data, ...)
                pf.retire(step, out.losses)      # recycles old generations
        finally:
            pf.close()
    """

    def __init__(self, sampler: GlasuSampler, schedule: Sequence[int],
                 n_buffers: int = 2, start: int = 0):
        if any(k < 1 for k in schedule):
            raise ValueError(f"step schedule must be positive: {schedule}")
        self.sampler = sampler
        self.schedule = list(schedule)
        self.start = start              # round before the first step
        self.n_buffers = max(1, min(int(n_buffers), len(self.schedule)))
        k_max = max(self.schedule, default=0)
        self._bufs: List[SampledBatch] = [
            self._alloc_generation(k_max) for _ in range(self.n_buffers)]
        self._free: "queue.Queue[int]" = queue.Queue()
        for g in range(self.n_buffers):
            self._free.put(g)
        self._out: "queue.Queue[Any]" = queue.Queue()
        self._inflight: List[tuple] = []     # (gen, output handle) FIFO
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._work, name="glasu-prefetch", daemon=True)
        self._thread.start()

    # ---------------------------------------------------------- allocation
    def _alloc_generation(self, k: int) -> SampledBatch:
        """One round-stacked scratch generation matching the sampler's
        static shapes (leading axis k)."""
        s = self.sampler
        cfg = s.cfg
        mk = lambda like: np.zeros((k,) + like.shape, like.dtype)
        gi, gm, rv, sp = [], [], [], []
        for l in range(cfg.n_layers):
            i, m, v, p = s._scratch[l]
            gi.append(mk(i))
            gm.append(mk(m))
            rv.append(mk(v))
            sp.append(mk(p))
        return SampledBatch(
            feats=mk(s._feat_scratch),
            gather_idx=tuple(gi), gather_mask=tuple(gm),
            row_valid=tuple(rv),
            labels=np.zeros((k, cfg.batch_size), np.int32),
            self_pos=tuple(sp))

    # -------------------------------------------------------------- worker
    def _work(self):
        """Per step: span ``fill`` (keyed by the step's first round)
        around ``wait_free`` (every generation in flight), one ``sample``
        per round, and the copies into the generation (``fill``'s own
        time)."""
        try:
            t = self.start
            for k in self.schedule:
                with obs.span("fill", key=t + 1):
                    with obs.span("wait_free"):
                        gen = self._free.get()
                    if gen == _STOP or self._stop.is_set():
                        return
                    buf = self._bufs[gen]
                    view = unstack_round(buf, slice(0, k))
                    for i in range(k):
                        if self._stop.is_set():  # close() mid-fill: exit
                            return               # promptly, step unfinished
                        with obs.span("sample"):
                            b = self.sampler.sample_round()
                        jax.tree.map(
                            lambda dst, src, i=i: np.copyto(dst[i], src),
                            view, b)
                    state = copy.deepcopy(
                        self.sampler.rng.bit_generator.state)
                    self._out.put(StepBatch(view, k, gen, state))
                t += k
        except BaseException as e:          # propagate to the consumer
            self._out.put(_WorkerError(e))

    # ------------------------------------------------------------ consumer
    def get(self) -> StepBatch:
        item = self._out.get()
        if isinstance(item, _WorkerError):
            raise RuntimeError("sampler prefetch worker failed") from item.exc
        return item

    def retire(self, step: StepBatch, sync_handle) -> None:
        """Register the step as dispatched; recycle the oldest generation
        once the pipeline is full, blocking on ITS computation only (the
        step currently in flight keeps running)."""
        self._inflight.append((step.gen, sync_handle))
        while len(self._inflight) >= self.n_buffers:
            gen, handle = self._inflight.pop(0)
            if handle is not None:
                with obs.span("wait_device"):
                    jax.block_until_ready(handle)
            self._free.put(gen)

    def close(self) -> None:
        self._stop.set()
        self._free.put(_STOP)
        while True:                          # unblock a worker stuck on put
            try:
                self._out.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)
        # drain whatever raced in between the final get_nowait and join
        while True:
            try:
                self._out.get_nowait()
            except queue.Empty:
                break
        self._inflight.clear()
