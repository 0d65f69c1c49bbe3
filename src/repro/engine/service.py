"""Traffic-serving front end for compiled programs.

``InferenceService`` serves classification requests through the shared
continuous-batching scheduler (``engine/scheduler.py``, the control plane
extracted from ``runtime/serve.py``'s ``ServeLoop``): an optionally
bounded request queue, a fixed number of batch slots refilled as they
free up, and per-request latency / occupancy metrics.

Every executed batch has the *same* ``[batch_slots, C, H, W]`` shape —
free slots ride along as zero-padded dead rows flagged by a validity
mask — so the jitted forward is traced exactly once, no matter how
requests arrive.  ``channel_norm`` is per-sample, which makes that safe:
a request's logits are bit-identical whether it runs alone, co-batched
with other requests, or next to dead slots.

With ``collect_stats=True`` every served batch also measures its
activation-skip counters (``engine/stats.py``); the validity mask
excludes dead slots from both the counters and the window totals, so the
accumulated ``activation_stats`` equal a one-shot stats forward over
exactly the served images and ``service.hardware_report()`` prices
energy from the skip probabilities *realized on the traffic actually
served* rather than an assumption.
"""

from __future__ import annotations

import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.executor import make_forward, warmup_forward
from repro.engine.program import CompiledNetwork
from repro.engine.scheduler import SlotScheduler
from repro.engine.stats import ActivationStats
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.api import Request as ServeRequest

__all__ = ["ClassifyRequest", "InferenceService"]


class ClassifyRequest(ServeRequest):
    """Deprecated: use :class:`repro.serve.Request` (``image=`` form)."""

    def __init__(self, image, logits=None, label=None, done: bool = False):
        warnings.warn(
            "repro.engine.service.ClassifyRequest is deprecated; use "
            "repro.serve.Request(image=...)",
            DeprecationWarning, stacklevel=2,
        )
        super().__init__(image=image, logits=logits, label=label, done=done)


class InferenceService:
    """Continuous-batching classification over a jitted engine forward."""

    def __init__(
        self,
        program: CompiledNetwork,
        batch_slots: int = 8,
        backend: str | None = None,
        interpret: bool | None = None,
        collect_stats: bool = False,
        mesh=None,
        partition=None,
        max_queue: int = 0,
        clock: Callable[[], float] = time.monotonic,
        tracer: Tracer | None = None,
    ):
        """With ``mesh=`` every batch executes sharded
        (``engine/partition.py``): batch slots split over the mesh's data
        axis, each layer's tiles over the model axis.  Because the batch
        shape is always the full ``batch_slots``, the data axis divides
        it whenever ``batch_slots % data == 0`` — partially filled
        batches shard exactly like full ones instead of falling back to
        replication.

        ``max_queue`` bounds the number of waiting requests (0 =
        unbounded); a full queue raises
        :class:`~repro.engine.scheduler.SchedulerFull` from
        :meth:`submit` — the backpressure signal under load.

        ``tracer`` puts the service on a shared Perfetto timeline: every
        request becomes an async span (enqueue -> admit -> done, via the
        scheduler), and queue depth / live slots counter tracks.  Each
        executed batch is a ``service.step`` span whatever the tracer
        (see :meth:`step`).  The tracer is *not* handed to the jitted
        forward — serving always runs the single-trace jitted path, whose
        per-layer device time a profile names by its ``<layer>/<stage>``
        scopes (``make_forward``).
        """
        self.program = program
        self.batch_slots = batch_slots
        self.collect_stats = collect_stats
        self.mesh = mesh
        self._forward = make_forward(
            program, backend=backend, interpret=interpret,
            collect_stats=collect_stats, mesh=mesh, partition=partition,
        )
        self._tracer = tracer or NULL_TRACER
        self._clock = clock
        self.scheduler = SlotScheduler(
            batch_slots, max_queue=max_queue, clock=clock, tracer=tracer
        )
        shape = self._input_shape()
        # persistent slot buffer: freed slots are zeroed, so the fixed
        # batch is always "live images + zero padding"
        self._slots_x = np.zeros((batch_slots, *shape), np.float32)
        self.batches_run = 0
        self.activation_stats: ActivationStats | None = None

    def _input_shape(self) -> tuple[int, int, int]:
        cfg = self.program.config
        return (cfg.in_channels, cfg.input_hw, cfg.input_hw)

    def trace_count(self) -> int:
        """How many times the underlying forward has been traced."""
        return self._forward.trace_count()

    def warmup(self) -> None:
        """Trace/compile the forward at the serving batch shape without
        sending traffic through the scheduler (metrics stay at zero)."""
        warmup_forward(self._forward, self.program, self.batch_slots)

    def lower(self):
        """``jax.jit(...).lower`` of the served forward at the fixed batch
        shape, to read the program the device runs; after :meth:`warmup`
        its ``compile()`` is an in-memory cache hit."""
        x = jnp.zeros((self.batch_slots, *self._input_shape()), jnp.float32)
        return self._forward.lower(x, jnp.zeros(self.batch_slots, bool))

    @property
    def metrics(self) -> dict:
        """Scheduler metrics: queue/latency/occupancy of the served load."""
        return self.scheduler.snapshot()

    def reset_stats(self) -> None:
        self.activation_stats = None

    def reset_metrics(self) -> None:
        """Start a fresh scheduler-metrics window (e.g. post warm-up)."""
        self.scheduler.reset_metrics()

    def _record_stats(self, stats: ActivationStats) -> None:
        self.activation_stats = (
            stats if self.activation_stats is None
            else self.activation_stats.merge(stats)
        )

    def _validate(self, img: np.ndarray) -> np.ndarray:
        shape = self._input_shape()
        img = np.asarray(img, np.float32)
        if img.shape != shape:
            raise ValueError(f"request image {img.shape} != expected {shape}")
        return img

    def submit(self, request: ServeRequest) -> ServeRequest:
        """Validate and enqueue one request (raises ``SchedulerFull`` when
        the bounded queue is full, ``ValueError`` on a bad image shape)."""
        request.image = self._validate(request.image)
        self.scheduler.submit(request)
        return request

    def try_submit(self, request: ServeRequest) -> bool:
        """Validate and enqueue; ``False`` when the bounded queue is full
        (the shed path the ``repro.serve`` session turns into
        ``Overloaded`` — ``SchedulerFull`` never escapes that route)."""
        request.image = self._validate(request.image)
        return self.scheduler.try_submit(request)

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def step(self) -> list[ServeRequest]:
        """Refill free slots from the queue and run one fixed-shape batch.

        Returns the requests completed by this batch (empty when there
        was nothing to serve).  A batch is a ``service.step`` span with
        four children, on the profiler's timeline whatever the tracer:
        ``service.refill`` (admission and the slot copies),
        ``service.dispatch`` (the host-to-device copy and the forward's
        launch; with ``collect_stats`` also the counters' fetch),
        ``service.wait`` (``device_get`` of the logits) and
        ``service.complete`` (the result scatter and the slot zeroing).
        The scheduler records refill + dispatch + complete as the step's
        host time and the wait as its wait time.
        """
        sched = self.scheduler
        if not sched.has_work():
            return []
        span, clock = self._tracer.span, self._clock
        t0 = clock()
        with span("service.step", cat="serve", batch_slots=self.batch_slots):
            with span("service.refill", cat="serve"):
                for slot, req in sched.refill():
                    self._slots_x[slot] = req.image
                valid = sched.valid_mask()
            with span("service.dispatch", cat="serve", live=int(valid.sum())):
                out = self._forward(jnp.asarray(self._slots_x), valid)
                if self.collect_stats:
                    out, stats = out
                    self._record_stats(stats)
            t1 = clock()
            with span("service.wait", cat="serve"):
                logits = np.asarray(jax.device_get(out))
            t2 = clock()
            with span("service.complete", cat="serve"):
                self.batches_run += 1
                sched.record_step()
                finished = []
                for slot, req in sched.live():
                    req.logits = logits[slot]
                    req.label = int(np.argmax(logits[slot]))
                    req.done = True
                    sched.complete(slot)
                    self._slots_x[slot] = 0.0  # dead slots stay zero-padded
                    finished.append(req)
            t3 = clock()
        sched.record_step_times((t1 - t0) + (t3 - t2), t2 - t1)
        return finished

    def run(self) -> list[ServeRequest]:
        """Serve until the queue and every slot are drained."""
        finished = []
        while self.scheduler.has_work():
            finished.extend(self.step())
        return finished

    def serve(self, requests: list[ServeRequest]) -> list[ServeRequest]:
        """Drain ``requests`` through the scheduler.

        All request shapes are validated *before* any batch runs, so a
        malformed request rejects the whole call up front instead of
        leaving earlier requests served and later ones untouched.
        Submission interleaves with serving, so a bounded queue never
        overflows from a large one-shot batch.
        """
        images = [self._validate(r.image) for r in requests]
        for r, img in zip(requests, images):
            r.image = img
        pending = list(requests)
        while pending or self.scheduler.has_work():
            # capacity probe, not try_submit: a full queue mid-drain is
            # backpressure handled here, not a rejection to count
            while pending and self.scheduler.has_capacity():
                self.scheduler.submit(pending.pop(0))
            self.step()
        return requests

    def classify(self, images: np.ndarray) -> np.ndarray:
        """Convenience: [N, C, H, W] -> labels [N]."""
        reqs = [ServeRequest(image=img) for img in np.asarray(images)]
        self.serve(reqs)
        return np.array([r.label for r in reqs], np.int64)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the scheduler metrics — what an
        RPC front end serves from its ``/metrics`` endpoint."""
        return self.scheduler.metrics.to_prometheus(prefix="engine_service")

    def hardware_report(self, assumed_skip: float | None = None, **kw) -> dict:
        """Crossbar pricing from the skip statistics of the served traffic.

        Falls back to the program's assumed/no-skip pricing when no
        requests have been served with ``collect_stats`` yet.
        """
        return self.program.hardware_report(
            skip_stats=self.activation_stats, assumed_skip=assumed_skip, **kw
        )
