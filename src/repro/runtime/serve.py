"""Serving runtime: prefill + decode steps and continuous-batching decode.

``make_prefill_step`` / ``make_decode_step`` build the jitted functions the
dry-run lowers for the decode_* / long_* shapes: one new token against a
KV cache of ``seq_len`` (cache donated, so decode is in-place in HBM).

:class:`DecodeService` is the continuous-batching generation backend:
per-slot decode positions (``pos [batch_slots]``) let the shared
:class:`~repro.engine.scheduler.SlotScheduler` admit a queued prompt into
a freed slot *while the other slots are mid-decode* — the vLLM model,
with the backend/metadata split keeping all per-request state (prompt
lengths, emitted counts, completion) host-side in the scheduler and only
fixed-shape arrays (``tokens [B]``, ``pos [B]``, the batched cache)
crossing into the traced function:

  * the decode step always runs at the fixed ``[batch_slots]`` shape and
    is traced exactly once (``trace_count()``); dead slots decode at
    position 0 into cache rows that the next admission overwrites;
  * admission prefills the prompt at its exact length on a fresh
    single-row cache and scatters that row into the batched cache
    (``make_slot_prefill``) — exact for recurrent SSM state too, where a
    padded batch prefill would fold pad garbage into the state.  Like
    vLLM, prefill compiles once per distinct prompt length
    (``prefill_trace_count()``); the single-trace invariant is a decode
    property;
  * a request's logits are bit-identical co-batched or solo: every
    per-row op (masked attention, SSM scan, sampling) is independent
    across batch rows.

:class:`ServeLoop` keeps the old drain-a-list-of-requests API on top of
it.  Requests are :class:`repro.serve.Request` (``prompt=`` form).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.scheduler import SlotScheduler
from repro.models.transformer import ModelConfig, apply_model, init_cache
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.api import Request

__all__ = [
    "ServeConfig",
    "make_prefill_step",
    "make_decode_step",
    "make_slot_prefill",
    "DecodeService",
    "ServeLoop",
]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_seq: int = 1024
    temperature: float = 0.0  # 0 -> greedy
    eos_id: int = 0
    cache_dtype: str = "bfloat16"


def make_prefill_step(cfg: ModelConfig, statics, scfg: ServeConfig):
    def prefill(params, cache, tokens, extras=None):
        """tokens: [B, S] -> (next_token [B], cache).  A VLM patch prefix
        (extras['prefix_embeds']) extends the context; positions and cache
        length cover prefix + tokens."""
        kwargs = dict(extras or {})
        total = tokens.shape[1]
        if "prefix_embeds" in kwargs:
            total += kwargs["prefix_embeds"].shape[1]
        logits, cache, _ = apply_model(
            params, statics, tokens,
            positions=jnp.arange(total),
            cache=cache, cache_pos=jnp.int32(0), cache_len=jnp.int32(total),
            **kwargs,
        )
        next_tok = jnp.argmax(logits[:, -1, : cfg.vocab], axis=-1)
        return next_tok.astype(jnp.int32), cache

    return prefill


def make_decode_step(cfg: ModelConfig, statics, scfg: ServeConfig):
    def decode(params, cache, tokens, pos, rng=None):
        """tokens: [B] last emitted; pos: the position to write — a
        scalar shared by every slot (legacy generational decode) or a
        [B] vector of per-slot positions (continuous batching)."""
        per_row = getattr(pos, "ndim", 0) > 0
        logits, cache, _ = apply_model(
            params, statics, tokens[:, None],
            positions=pos[:, None] if per_row else pos[None],
            cache=cache, cache_pos=pos, cache_len=pos + 1,
        )
        logits = logits[:, -1, : cfg.vocab].astype(jnp.float32)
        if scfg.temperature > 0 and rng is not None:
            next_tok = jax.random.categorical(
                rng, logits / scfg.temperature, axis=-1
            )
        else:
            next_tok = jnp.argmax(logits, axis=-1)
        return next_tok.astype(jnp.int32), cache

    return decode


def _scatter_cache_row(batch_cache, row_cache, slot):
    """Write the single-row ``row_cache`` pytree into row ``slot`` of the
    batched cache.  The cache pytree has heterogeneous batch axes: prefix
    layers and the encoder memory carry batch on axis 0, the scanned body
    stacks periods in front so batch sits on axis 1."""

    def write(dst, src, axis):
        start = [jnp.int32(0)] * dst.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), tuple(start)
        )

    out = {
        "prefix_layers": [
            jax.tree.map(lambda d, s: write(d, s, 0), d_, s_)
            for d_, s_ in zip(
                batch_cache["prefix_layers"], row_cache["prefix_layers"]
            )
        ],
        "body": [
            jax.tree.map(lambda d, s: write(d, s, 1), d_, s_)
            for d_, s_ in zip(batch_cache["body"], row_cache["body"])
        ],
    }
    if "memory" in batch_cache:
        out["memory"] = write(
            batch_cache["memory"], row_cache["memory"], 0
        )
    return out


def make_slot_prefill(cfg: ModelConfig, statics, scfg: ServeConfig):
    def prefill(params, caches, tokens, slot):
        """tokens: [1, L] exact-length prompt; slot: scalar slot index.

        Prefills a fresh single-row cache at the prompt's exact length —
        no padding, so recurrent (SSM) state is exact — then scatters the
        row into the batched cache at ``slot``.  Returns
        (first sampled token [], updated batched caches)."""
        length = tokens.shape[1]
        row = init_cache(
            statics, 1, scfg.max_seq, dtype=jnp.dtype(scfg.cache_dtype)
        )
        logits, row, _ = apply_model(
            params, statics, tokens,
            positions=jnp.arange(length),
            cache=row, cache_pos=jnp.int32(0), cache_len=jnp.int32(length),
        )
        caches = _scatter_cache_row(caches, row, slot)
        next_tok = jnp.argmax(logits[0, -1, : cfg.vocab])
        return next_tok.astype(jnp.int32), caches

    return prefill


def _counted(fn, box: list):
    def wrapped(*args, **kwargs):
        box[0] += 1
        return fn(*args, **kwargs)

    return wrapped


class DecodeService:
    """Continuous-batching token generation over per-slot decode positions.

    Speaks the same step-based verb set as
    ``engine.service.InferenceService`` — ``submit``/``try_submit`` to
    enqueue a :class:`repro.serve.Request` (``prompt`` set), ``step()``
    to admit + advance one decode step, ``run()`` to drain — so the
    ``repro.serve`` session facade and HTTP server drive either backend
    identically.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        statics,
        params,
        scfg: ServeConfig,
        max_queue: int = 0,
        clock: Callable[[], float] = time.monotonic,
        tracer: Tracer | None = None,
        capture_logits: bool = False,
    ):
        self.cfg, self.statics, self.scfg = cfg, statics, scfg
        self.params = params
        self._tracer = tracer or NULL_TRACER
        self.scheduler = SlotScheduler(
            scfg.batch_slots, max_queue=max_queue, clock=clock, tracer=tracer
        )
        self.caches = init_cache(
            statics, scfg.batch_slots, scfg.max_seq,
            dtype=jnp.dtype(scfg.cache_dtype),
        )
        self._decode_traces = [0]
        self._prefill_traces = [0]
        decode_fn = make_decode_step(cfg, statics, scfg)
        self.capture_logits = capture_logits
        if capture_logits:
            # debug/test variant: also return the [B, vocab] decode
            # logits (still one jitted callable, still traced once)
            def decode_with_logits(params, cache, tokens, pos):
                logits, cache, _ = apply_model(
                    params, statics, tokens[:, None],
                    positions=pos[:, None], cache=cache,
                    cache_pos=pos, cache_len=pos + 1,
                )
                logits = logits[:, -1, : cfg.vocab].astype(jnp.float32)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return tok, logits, cache

            decode_fn = decode_with_logits
        self._decode = jax.jit(
            _counted(decode_fn, self._decode_traces), donate_argnums=(1,)
        )
        self._prefill = jax.jit(
            _counted(make_slot_prefill(cfg, statics, scfg),
                     self._prefill_traces),
            donate_argnums=(1,),
        )
        self._tokens = np.zeros(scfg.batch_slots, np.int32)
        self._pos = np.zeros(scfg.batch_slots, np.int32)
        self.last_logits: np.ndarray | None = None  # capture_logits only
        self.steps_run = 0

    # ------------------------------------------------------------ admission

    def trace_count(self) -> int:
        """How many times the fixed-shape decode step has been traced
        (the single-trace invariant: 1 for any traffic pattern)."""
        return self._decode_traces[0]

    def prefill_trace_count(self) -> int:
        """Prefill traces = number of distinct prompt lengths served."""
        return self._prefill_traces[0]

    @property
    def metrics(self) -> dict:
        return self.scheduler.snapshot()

    def metrics_text(self) -> str:
        return self.scheduler.metrics.to_prometheus(prefix="decode_service")

    def reset_metrics(self) -> None:
        self.scheduler.reset_metrics()

    def _validate(self, request: Request) -> Request:
        if request.prompt is None:
            raise ValueError("generation request needs a prompt")
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size < 1 or prompt.size > self.scfg.max_seq:
            raise ValueError(
                f"prompt length {prompt.size} outside [1, "
                f"{self.scfg.max_seq}]"
            )
        request.prompt = prompt
        return request

    def submit(self, request: Request) -> Request:
        """Validate + enqueue (raises ``SchedulerFull`` when bounded
        queue is full — front ends should use ``try_submit``)."""
        self.scheduler.submit(self._validate(request))
        return request

    def try_submit(self, request: Request) -> bool:
        return self.scheduler.try_submit(self._validate(request))

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    # ------------------------------------------------------------- stepping

    def _finish(self, slot: int, req: Request, finished: list) -> None:
        req.done = True
        self.scheduler.complete(slot)
        self._tokens[slot] = 0
        self._pos[slot] = 0
        finished.append(req)

    def step(self) -> list[Request]:
        """Admit queued prompts into free slots (prefill), then advance
        every live slot one decode step at its own position.  Returns the
        requests completed by this step."""
        sched = self.scheduler
        scfg = self.scfg
        finished: list[Request] = []
        was_decoding = bool(sched.live())
        for slot, req in sched.refill():
            prompt = np.asarray(req.prompt, np.int32)[None]
            with self._tracer.span(
                "serve.prefill", cat="serve", slot=slot, len=prompt.shape[1]
            ):
                tok, self.caches = self._prefill(
                    self.params, self.caches, jnp.asarray(prompt),
                    jnp.int32(slot),
                )
                t = int(jax.device_get(tok))
            req.output.append(t)
            self._tokens[slot] = t
            self._pos[slot] = prompt.shape[1]
            sched.record_first_result(slot)
            if was_decoding:
                # the mid-decode admission instant: this slot was refilled
                # while other slots were already between decode steps
                self._tracer.async_instant(
                    "request", sched.slot_rid(slot), cat="request",
                    event="admit_mid_decode", slot=slot,
                    pos=int(prompt.shape[1]),
                )
            if (
                t == scfg.eos_id
                or len(req.output) >= req.max_new_tokens
                or self._pos[slot] >= scfg.max_seq
            ):
                self._finish(slot, req, finished)
        live = sched.live()
        if not live:
            return finished
        with self._tracer.span("serve.decode", cat="serve", live=len(live)):
            out = self._decode(
                self.params, self.caches, jnp.asarray(self._tokens),
                jnp.asarray(self._pos),
            )
            if self.capture_logits:
                tok, logits, self.caches = out
                self.last_logits = np.asarray(jax.device_get(logits))
            else:
                tok, self.caches = out
            tok_np = np.asarray(jax.device_get(tok))
        self.steps_run += 1
        sched.record_step()
        for slot, req in live:
            t = int(tok_np[slot])
            self._tokens[slot] = t
            self._pos[slot] += 1
            req.output.append(t)
            if (
                t == scfg.eos_id
                or len(req.output) >= req.max_new_tokens
                or self._pos[slot] >= scfg.max_seq
            ):
                self._finish(slot, req, finished)
        return finished

    def run(self) -> list[Request]:
        """Serve until the queue and every slot are drained."""
        finished: list[Request] = []
        while self.has_work():
            finished.extend(self.step())
        return finished


class ServeLoop:
    """Drain-a-list-of-requests wrapper over :class:`DecodeService`.

    Admission is now *continuous*: a freed slot refills from the queue on
    the very next step while the remaining slots keep decoding at their
    own per-slot positions (the old generational loop waited for the
    whole batch to finish).  ``loop.metrics`` carries the scheduler
    snapshot after :meth:`generate`.
    """

    def __init__(self, cfg: ModelConfig, statics, params, scfg: ServeConfig,
                 tracer: Tracer | None = None):
        self.cfg, self.statics, self.scfg = cfg, statics, scfg
        self.params = params
        self.tracer = tracer or NULL_TRACER
        self.service = DecodeService(
            cfg, statics, params, scfg, tracer=tracer
        )
        self.metrics: dict | None = None

    def generate(self, requests: list[Request]) -> list[Request]:
        for r in requests:
            self.service.submit(r)
        self.service.run()
        self.metrics = self.service.scheduler.snapshot()
        return requests
