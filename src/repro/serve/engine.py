"""Serving engines with AFT-backed atomic weight refresh.

The serving-side instance of the paper's problem: a trainer (or fine-tuning
job) publishes new weights as multi-key checkpoint transactions while
replicas serve traffic.  Without atomic visibility a replica hot-swapping
weights can assemble a *torn* parameter set — layer 7 from step 1000,
layer 8 from step 900 (a fractured read, §2.1).  The engines' refreshers
restore inside one AFT read transaction, so read-atomic isolation makes
the swap all-or-nothing; ``benchmarks/table2.py`` measures exactly this
anomaly class on plain storage.

Two engines share that refresh contract:

* ``ServeEngine`` — the static baseline: prompts bucketed by length, one
  batch decoded to completion before the next is admitted.  Every distinct
  (batch, prompt-length) shape recompiles the jitted prefill, and every
  request in a bucket decodes until the *longest* request finishes.
* ``ContinuousEngine`` — a continuous-batching decode loop: a fixed-slot,
  shape-stable decode state that requests join and leave mid-flight.
  Prompts prefill in fixed-size chunks interleaved between decode
  iterations (long prompts never stall the batch), admission is by free
  slots, and the one jitted decode/prefill pair compiles exactly once —
  shapes never change.  Free slots ride through decode with a sentinel
  position of ``max_len``, which the masked cache write turns into a
  no-op.

Both engines swap weights only **between** iterations (the loop snapshots
``self._params`` once per iteration under the lock), so a forward pass
never mixes two weight versions.  ``install_weights`` emits a
``weight_refresh`` trace span carrying the publishing transaction's UUID,
letting ``obs/checker.py`` correlate a replica's swap with the publish
commit in replayed traces.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AftCheckpointer, CheckpointNotFound
from repro.models import Model
from repro.obs import trace as obs_trace
from repro.obs.registry import Registry
from repro.obs.trace import region

_stats_deprecation_warned = False


class EngineStats(dict):
    """Counter map that is also callable (the ``AftNode.stats`` shim):
    dict access keeps the historical ``engine.stats["tokens_out"]``
    surface, calling it returns the engine registry's full snapshot.
    New code should read ``engine.registry.snapshot()`` directly."""

    def __init__(self, counters: Dict[str, int], snapshot_fn):
        super().__init__(counters)
        self._snapshot_fn = snapshot_fn

    def __call__(self) -> Dict[str, object]:
        global _stats_deprecation_warned
        if not _stats_deprecation_warned:
            _stats_deprecation_warned = True
            warnings.warn(
                "engine.stats() is a deprecated read path; use "
                "engine.registry.snapshot() (repro.obs.registry) instead",
                DeprecationWarning, stacklevel=2)
        return self._snapshot_fn()


@dataclass
class ServeConfig:
    max_batch: int = 8                # static path: prompts per bucket
    max_len: int = 256                # KV-cache rows per request/slot
    temperature: float = 0.0          # 0 → greedy
    refresh_every_s: float = 1.0
    # --- continuous batching (ContinuousEngine) ---
    slots: int = 8                    # fixed decode-state width
    prefill_chunk: int = 16           # prompt tokens fed per prefill chunk
    prefill_chunks_per_iter: int = 1  # chunks interleaved per decode iter
    seed: int = 0                     # sampling seed (temperature > 0)


def _jit_cache_size(fn) -> int:
    """Number of compiled variants behind a jitted callable (-1 when the
    running jax has no counter).  The continuous engine's tests assert this
    stays at 1 — shape-stable means compile-once."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


class _WeightedEngine:
    """Shared weight/refresh/observability plumbing for both engines."""

    def __init__(self, model: Model, checkpointer: Optional[AftCheckpointer],
                 config: Optional[ServeConfig], params: Optional[Any],
                 registry: Optional[Registry], name: str):
        self.model = model
        self.ckpt = checkpointer
        # fresh default per engine — a dataclass default instance would be
        # shared (and mutated through) every engine built without a config
        self.config = config if config is not None else ServeConfig()
        self.name = name
        self._params = None if params is None else jax.device_put(params)
        self._weights_step = -1
        self._lock = threading.Lock()
        self._stop_refresh = threading.Event()
        self._refresher: Optional[threading.Thread] = None
        self.registry = registry or Registry(name=name)
        self.stats = EngineStats(
            {"refreshes": 0, "refresh_errors": 0, "requests": 0,
             "tokens_out": 0},
            self.registry.snapshot)
        self.refresh_error: Optional[BaseException] = None
        self.registry.attach_counters(self.stats)
        self._h_refresh = self.registry.histogram("refresh.latency")

    # ------------------------------------------------------------- weights
    def install_weights(self, params: Any, step: int,
                        publish_uuid: Optional[str] = None,
                        dur_ms: float = 0.0) -> bool:
        """Swap the serving weights (between iterations — the decode loop
        reads ``self._params`` once per iteration).  Returns False when
        ``step`` is not newer than the installed set.  The tree is placed
        on the device here, once, so no jitted call copies host weights
        again.  Emits a ``weight_refresh`` span carrying the publishing
        transaction's UUID so the offline checker can correlate the swap
        with the publish."""
        if step <= self._weights_step:
            return False
        params = jax.block_until_ready(jax.device_put(params))
        with self._lock:
            if step <= self._weights_step:
                return False
            self._params = params
            self._weights_step = step
            self.stats["refreshes"] += 1
        tracer = obs_trace.get_tracer()
        if tracer.enabled:
            trace = (obs_trace.txn_trace_id(publish_uuid) if publish_uuid
                     else obs_trace.trace_id(self.name))
            tracer.emit(
                "span", name="weight_refresh", trace=trace,
                span=obs_trace.span_id(trace, "weight_refresh",
                                       f"{self.name}@{step}"),
                parent=None, dur_ms=round(dur_ms, 3), status="ok",
                publish_uuid=publish_uuid, step=step, engine=self.name)
        return True

    def refresh_weights(self) -> bool:
        """Atomically load the latest committed checkpoint.  Returns True
        if a newer weight set was installed."""
        if self.ckpt is None:
            return False
        t0 = time.perf_counter()
        try:
            like = {"params": self.model.abstract_params()}
            step, tree, _ = self.ckpt.restore(like=like)
        except CheckpointNotFound:
            return False
        dur = time.perf_counter() - t0
        self._h_refresh.observe_s(dur)
        return self.install_weights(tree["params"], step,
                                    publish_uuid=self.ckpt._save_uuid(step),
                                    dur_ms=dur * 1e3)

    def start_refresher(self) -> None:
        def loop():
            while not self._stop_refresh.wait(self.config.refresh_every_s):
                try:
                    self.refresh_weights()
                except Exception as exc:  # retried next round, but counted
                    self.refresh_error = exc
                    self.stats["refresh_errors"] += 1

        self._refresher = threading.Thread(target=loop, daemon=True)
        self._refresher.start()

    def stop(self) -> None:
        self._stop_refresh.set()
        if self._refresher is not None:
            self._refresher.join(timeout=5)
            self._refresher = None

    @property
    def weights_step(self) -> int:
        return self._weights_step

    def current_params(self):
        with self._lock:
            return self._params, self._weights_step


class ServeEngine(_WeightedEngine):
    """Static length-bucketed batch serving (the baseline the continuous
    engine is measured against in ``benchmarks/fig_serve.py``)."""

    def __init__(self, model: Model, checkpointer: Optional[AftCheckpointer],
                 config: Optional[ServeConfig] = None,
                 params: Optional[Any] = None, *,
                 registry: Optional[Registry] = None, name: str = "serve"):
        super().__init__(model, checkpointer, config, params, registry, name)
        max_len = self.config.max_len

        def prefill(params, tokens):
            return model.prefill(params, tokens, max_len)

        def decode(params, state, tokens, position):
            logits, state = model.decode_step(params, state, tokens, position)
            return logits, state

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)

    def compile_counts(self) -> Dict[str, int]:
        return {"prefill": _jit_cache_size(self._prefill),
                "decode": _jit_cache_size(self._decode)}

    # ------------------------------------------------------------- serving
    def _sample(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        t = self.config.temperature
        if t <= 0:
            return jnp.argmax(logits[:, -1, :], axis=-1)
        return jax.random.categorical(key, logits[:, -1, :] / t, axis=-1)

    def generate(self, prompts: Sequence[Sequence[int]], max_new: int,
                 seed: int = 0) -> List[List[int]]:
        """Batched generation.  Prompts in one call must share a length
        (callers bucket by length — standard prefill bucketing)."""
        assert prompts, "empty batch"
        plen = len(prompts[0])
        assert all(len(p) == plen for p in prompts), "bucket by length"
        assert plen + max_new <= self.config.max_len
        with self._lock:
            params = self._params
        assert params is not None, "no weights loaded"
        self.stats["requests"] += len(prompts)

        tokens = jnp.asarray(np.asarray(prompts, np.int32))
        _, state = self._prefill(params, tokens)
        # the last prompt token's logits come from decode of that token at
        # its position: re-run the final position for the first new token
        out: List[List[int]] = [[] for _ in prompts]
        key = jax.random.key(seed)
        cur = tokens[:, -1:]
        position = plen - 1
        for i in range(max_new):
            key, sub = jax.random.split(key)
            logits, state = self._decode(params, state, cur,
                                         jnp.int32(position + i))
            nxt = self._sample(logits, sub)
            cur = nxt[:, None].astype(jnp.int32)
            toks = np.asarray(nxt).tolist()
            for b, tok in enumerate(toks):
                out[b].append(int(tok))
            self.stats["tokens_out"] += len(prompts)
        return out


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

class GenTicket:
    """Handle for one in-flight request; resolves when it leaves the batch.

    Its stamps are ``time.perf_counter()`` readings, each set once:
    ``submitted_at`` when it is queued, ``admitted_at`` when it takes a
    slot, ``first_token_at`` when its last prompt chunk yields the first
    token, and ``finished_at`` when it leaves the batch."""

    __slots__ = ("tokens", "prompt_len", "submitted_at", "admitted_at",
                 "first_token_at", "finished_at", "error", "_done")

    def __init__(self, prompt_len: int):
        self.tokens: List[int] = []
        self.prompt_len = prompt_len
        self.submitted_at = time.perf_counter()
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in flight")
        if self.error is not None:
            raise self.error
        return list(self.tokens)


class _SlotReq:
    __slots__ = ("ticket", "prompt", "max_new", "offset")

    def __init__(self, ticket: GenTicket, prompt: List[int], max_new: int):
        self.ticket = ticket
        self.prompt = prompt
        self.max_new = max_new
        self.offset = 0  # prompt tokens already prefilled


def _slice_slot(state, slot):
    """One slot's decode state: the stacked pattern carries batch on axis 1
    (axis 0 is layers), tail blocks carry batch on axis 0."""
    out = {"pattern": jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
        state["pattern"])}
    if "tail" in state:
        out["tail"] = jax.tree.map(
            lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=0),
            state["tail"])
    return out


def _update_slot(state, sub, slot):
    out = {"pattern": jax.tree.map(
        lambda l, s: jax.lax.dynamic_update_slice_in_dim(
            l, s.astype(l.dtype), slot, axis=1),
        state["pattern"], sub["pattern"])}
    if "tail" in state:
        out["tail"] = jax.tree.map(
            lambda l, s: jax.lax.dynamic_update_slice_in_dim(
                l, s.astype(l.dtype), slot, axis=0),
            state["tail"], sub["tail"])
    return out


class ContinuousEngine(_WeightedEngine):
    """Continuous-batching decode loop over a fixed-slot decode state.

    Requests join free slots mid-flight and leave as soon as their own
    ``max_new`` is reached; prompts prefill in fixed ``prefill_chunk``-sized
    chunks interleaved between decode iterations.  All jitted shapes are
    functions of (slots, prefill_chunk, max_len) only, so the decode/prefill
    pair compiles exactly once per engine — ``compile_counts()`` exposes the
    jit cache sizes for tests to assert on.

    Drive it either manually (``step()`` per iteration — deterministic, used
    by tests) or with the background loop (``start()`` / ``stop()``).  The
    prompt's padded prefill footprint (``ceil(len(prompt)/chunk) * chunk``)
    and ``len(prompt) + max_new`` must both fit in ``max_len``.
    """

    def __init__(self, model: Model, checkpointer: Optional[AftCheckpointer]
                 = None, config: Optional[ServeConfig] = None,
                 params: Optional[Any] = None, *,
                 registry: Optional[Registry] = None,
                 name: str = "continuous"):
        super().__init__(model, checkpointer, config, params, registry, name)
        if not model.supports_chunked_prefill:
            raise NotImplementedError(
                "continuous batching needs chunked prefill; block kinds "
                f"{sorted(set(model.cfg.pattern) | set(model.cfg.tail))} "
                "include non-attention state")
        cfg = self.config
        S, L, C = int(cfg.slots), int(cfg.max_len), int(cfg.prefill_chunk)
        assert 0 < C <= L, "prefill_chunk must fit max_len"
        self._S, self._L, self._C = S, L, C
        temp = float(cfg.temperature)

        def sample(logits, key):
            if temp <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / temp, axis=-1).astype(jnp.int32)

        def decode(params, state, tokens, positions, key):
            logits, state = model.decode_step(params, state,
                                              tokens[:, None], positions)
            return sample(logits[:, -1, :], key), state

        def prefill(params, state, slot, tokens, offset, last_index, key):
            sub = _slice_slot(state, slot)
            logits, sub = model.prefill_chunk(params, sub,
                                              tokens[None, :], offset)
            state = _update_slot(state, sub, slot)
            nxt = sample(jnp.take(logits[0], last_index, axis=0), key)
            return nxt, state

        # donate the decode state: it is rewritten in place every iteration
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._prefill = jax.jit(prefill, donate_argnums=(1,))

        self._state = model.init_decode_state(S, L)
        self._tokens = np.zeros((S,), np.int32)
        # position == max_len is the free-slot sentinel: the masked cache
        # write touches nothing and the row attends an empty prefix
        self._positions = np.full((S,), L, np.int32)
        self._slots: List[Optional[_SlotReq]] = [None] * S
        self._queue: deque = deque()
        self._qlock = threading.Lock()
        self._work = threading.Event()
        self._loop_stop = threading.Event()
        self._loop: Optional[threading.Thread] = None
        self.loop_error: Optional[BaseException] = None
        self._base_key = jax.random.key(int(cfg.seed))
        self._iter = 0
        self.stats.update({"decode_iters": 0, "prefill_chunks": 0,
                           "completed": 0, "queue_peak": 0})
        self.registry.gauge("active_slots").set_fn(
            lambda: int(np.sum(self._positions < self._L)))

    def compile_counts(self) -> Dict[str, int]:
        return {"prefill": _jit_cache_size(self._prefill),
                "decode": _jit_cache_size(self._decode)}

    # ------------------------------------------------------------- requests
    def submit(self, prompt: Sequence[int], max_new: int) -> GenTicket:
        prompt = [int(t) for t in prompt]
        assert prompt and max_new >= 1, "need a prompt and max_new >= 1"
        footprint = -(-len(prompt) // self._C) * self._C
        assert footprint <= self._L and len(prompt) + max_new <= self._L, (
            f"prompt {len(prompt)} (+{max_new} new) does not fit "
            f"max_len {self._L} with chunk {self._C}")
        ticket = GenTicket(len(prompt))
        with self._qlock:
            if self.loop_error is not None:
                ticket.error = self.loop_error
                ticket._done.set()
                return ticket
            self._queue.append(_SlotReq(ticket, prompt, int(max_new)))
            self.stats["requests"] += 1
            self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                           len(self._queue))
        self._work.set()
        return ticket

    def _key_for(self, n: int) -> jax.Array:
        if self.config.temperature <= 0:
            return self._base_key  # unused by greedy sampling
        return jax.random.fold_in(self._base_key, n)

    def _finish(self, slot: int) -> None:
        req = self._slots[slot]
        self._slots[slot] = None
        self._tokens[slot] = 0
        self._positions[slot] = self._L
        req.ticket.finished_at = time.perf_counter()
        req.ticket._done.set()
        self.stats["completed"] += 1

    def _emit(self, slot: int, tok: int, pos: int) -> None:
        """Hand ``tok``, the token at position ``pos``, to the slot's
        request, and free the slot once the request has all it asked for
        or the cache is full."""
        req = self._slots[slot]
        req.ticket.tokens.append(tok)
        self.stats["tokens_out"] += 1
        if len(req.ticket.tokens) >= req.max_new or pos >= self._L:
            self._finish(slot)
        else:
            self._tokens[slot] = tok
            self._positions[slot] = pos

    # ------------------------------------------------------------- the loop
    def step(self) -> bool:
        """One engine iteration: admit queued requests into free slots,
        advance up to ``prefill_chunks_per_iter`` prompt chunks, then run
        one batched decode over every active slot.  Returns True if any
        work was done.  Weights are read once at iteration start — a swap
        mid-iteration takes effect next iteration, never mid-forward.

        Each phase is a ``region`` on the profiler's clock, inside
        ``aft.engine.step``: ``admit``, ``prefill`` (chunk build, transfer,
        dispatch), ``prefill_sync`` and ``decode_sync`` (waiting for the
        sampled tokens), ``decode`` (transfer, dispatch) and ``emit``
        (handing tokens to requests)."""
        with region("aft.engine.step"):
            return self._iterate()

    def _iterate(self) -> bool:
        with self._lock:
            params = self._params
        if params is None:
            return False
        did = False
        with region("aft.engine.admit"), self._qlock:
            for s in range(self._S):
                if self._slots[s] is None and self._queue:
                    req = self._queue.popleft()
                    req.ticket.admitted_at = time.perf_counter()
                    self._slots[s] = req

        budget = int(self.config.prefill_chunks_per_iter)
        for s in range(self._S):
            if budget <= 0:
                break
            req = self._slots[s]
            if req is None or req.offset >= len(req.prompt):
                continue
            did = True
            budget -= 1
            with region("aft.engine.prefill"):
                plen = len(req.prompt)
                off = req.offset
                chunk = req.prompt[off:off + self._C]
                is_final = off + len(chunk) >= plen
                last_index = len(chunk) - 1
                if len(chunk) < self._C:  # pad the final chunk to fixed shape
                    chunk = chunk + [0] * (self._C - len(chunk))
                nxt, self._state = self._prefill(
                    params, self._state, jnp.int32(s),
                    jnp.asarray(chunk, jnp.int32), jnp.int32(off),
                    jnp.int32(last_index), self._key_for(self._iter * 2 + 1))
            req.offset = min(off + self._C, plen)
            self.stats["prefill_chunks"] += 1
            if is_final:
                # final chunk yields the first generated token (logits at
                # the last prompt position); the request turns active
                with region("aft.engine.prefill_sync"):
                    tok = int(np.asarray(nxt))
                with region("aft.engine.emit"):
                    req.ticket.first_token_at = time.perf_counter()
                    self._emit(s, tok, plen)

        active = [s for s in range(self._S) if self._positions[s] < self._L]
        if active:
            did = True
            with region("aft.engine.decode"):
                nxt, self._state = self._decode(
                    params, self._state, jnp.asarray(self._tokens),
                    jnp.asarray(self._positions),
                    self._key_for(self._iter * 2))
            with region("aft.engine.decode_sync"):
                nxt = np.asarray(nxt)
            self.stats["decode_iters"] += 1
            with region("aft.engine.emit"):
                for s in active:
                    self._emit(s, int(nxt[s]), int(self._positions[s]) + 1)
        self._iter += 1
        return did

    def start(self) -> None:
        """Run the decode loop on a background thread."""
        if self._loop is not None:
            return
        self._loop_stop.clear()

        def loop():
            try:
                while not self._loop_stop.is_set():
                    if not self.step():
                        with region("aft.engine.wait_work"):
                            self._work.clear()
                            self._work.wait(timeout=0.02)
            except Exception as exc:
                # a dead loop must not leave waiters hanging until their
                # timeout: fail everything in flight with the cause
                with self._qlock:
                    self.loop_error = exc
                self._fail_pending(exc)

        self._loop = threading.Thread(target=loop, daemon=True,
                                      name=f"{self.name}-decode")
        self._loop.start()

    def stop(self) -> None:
        self._loop_stop.set()
        self._work.set()
        if self._loop is not None:
            self._loop.join(timeout=30)
            self._loop = None
        # fail whatever is still in flight so waiters unblock
        self._fail_pending(RuntimeError(
            f"engine {self.name} stopped mid-request"))
        super().stop()

    def _fail_pending(self, error: BaseException) -> None:
        with self._qlock:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending + [r for r in self._slots if r is not None]:
            if not req.ticket.done():
                req.ticket.error = error
                req.ticket._done.set()
