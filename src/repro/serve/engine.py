"""KV-cache serving engine: continuous batching, chunked prefill, and
KV session reuse.

A fixed pool of ``n_slots`` sequence slots shares one batched cache
pytree.  New requests prefill into a free slot (B=1 prefill, scatter at
the cache's batch dim — located via the cache's logical axes); every
``step()`` decodes *all* active slots in lockstep with per-slot positions
(the vector-``pos`` decode path).  Finished slots free immediately and
the next queued request takes over — classic continuous batching.

**Chunked prefill** (``chunk_tokens > 0``): instead of one monolithic
prompt pass that monopolizes the step loop, the prompt lands in
fixed-size chunks — one chunk per ``step()``, interleaved with the
decode of every other active slot — so a long prompt no longer hides the
TTFT of queued short requests behind it.  The last chunk is padded to
the fixed size (one jit compile for any prompt length; the padded
garbage K/V sit *above* the live position and are overwritten by decode
writes before any query can attend them).  Requires
``model.supports_chunked_prefill`` (attention-family blocks only);
otherwise the engine silently falls back to monolithic prefill.

**KV sessions** (``session_cap > 0``): when a request carries a
``session_id``, the slot's KV cache stays *pinned in its slot* after the
request finishes (``slot_req`` is freed; the session table remembers the
slot, the token history and the live position).  A follow-up submit with
the same ``session_id`` whose prompt extends the cached history resumes
from the cached position — only the suffix is prefilled (through the
chunk path, at an offset).  Pinned slots are evicted LRU-first whenever
a fresh request needs a slot or the table exceeds ``session_cap``;
correctness never depends on the cache (a miss is just a full prefill).

**Sampling on the device**: every token is chosen by one jitted
sampler (``jit_sample_tokens``) over a call's logits — all the slots
of a decode step, or the last real row of a prompt's last chunk —
greedy where a row's temperature is 0, drawn under the engine's
device-held key elsewhere; the host reads back one small token array
per call (``token_reads``).

**Phases and work counters**: ``step()`` runs in named phases
(``PHASES``: admit, slot copy, prefill chunk, decode, sample, all inside
``serve.step``), each a profiler annotation on the device trace's clock
and a pair of ``serve.engine.phase_*`` counters; ``stats()`` carries the
per-engine totals with the decode steps and tokens and the prefill
chunks and real tokens they moved, the token reads, and, for a mixture
of experts, the routed assignments and the expert rows the dispatch
computed.

The Mercury serving gateway (services/gateway.py) drives this engine from
RPC handlers; ``generate()`` is the synchronous convenience wrapper used
by examples and tests.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import Model, unzip
from ..models.common import P, is_p
from ..telemetry import metrics as _metrics
from ..telemetry.phases import Phases

# unified metrics (fab.metrics exports these; the per-engine view is in
# stats()/gen.stats): session reuse and the step loop's work
_M_PREFIX_HITS = _metrics.counter("serve.engine.prefix_hits")
_M_PREFIX_MISSES = _metrics.counter("serve.engine.prefix_misses")
_M_TOKENS_SAVED = _metrics.counter("serve.engine.prefix_tokens_saved")
_M_EVICTIONS = _metrics.counter("serve.engine.session_evictions")
_M_DECODE_STEPS = _metrics.counter("serve.engine.decode_steps")
_M_DECODE_TOKENS = _metrics.counter("serve.engine.decode_tokens")
_M_PREFILL_CHUNKS = _metrics.counter("serve.engine.prefill_chunks")
_M_PREFILL_TOKENS = _metrics.counter("serve.engine.prefill_tokens")
_M_MOE_ASSIGNMENTS = _metrics.counter("serve.engine.moe_assignments")
_M_MOE_ROWS = _metrics.counter("serve.engine.moe_rows")
_M_TOKEN_READS = _metrics.counter("serve.engine.token_reads")

# the phases of step(), each a profiler annotation plus a pair of
# counters by phase; all nest in serve.step
PHASES = ("serve.step", "serve.admit", "serve.slot_copy",
          "serve.prefill_chunk", "serve.decode", "serve.sample")
_M_PHASE_NS = {p: _metrics.counter("serve.engine.phase_ns", phase=p)
               for p in PHASES}
_M_PHASE_CALLS = {p: _metrics.counter("serve.engine.phase_calls", phase=p)
                  for p in PHASES}

# chunk size used for session *resume* when chunked prefill is otherwise
# disabled (the resume path is built on prefill-at-an-offset)
_RESUME_CHUNK = 32


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 32
    temperature: float = 0.0           # 0 = greedy
    eos_id: int = -1                   # -1 = never
    frontend: Optional[np.ndarray] = None
    session_id: Optional[str] = None   # KV-session key (None = stateless)
    out_tokens: List[int] = field(default_factory=list)
    done_event: threading.Event = field(default_factory=threading.Event)
    on_token: Optional[Callable[[int, int], None]] = None
    # monotonic time of submit(); the gateway derives submit→done
    # turnaround (queue wait included) from this stamp
    t_submit: float = 0.0
    # monotonic time the request took a slot (prefill start); the
    # gateway's AdmissionController measures its *pure service time*
    # EWMA (slot occupancy, admit→done) from this, keeping queue wait
    # out of the shedding estimate
    t_admit: float = 0.0
    # monotonic time of the first emitted token (TTFT = t_first-t_submit)
    t_first: float = 0.0
    # set when the engine failed the request instead of finishing it
    error: Optional[str] = None
    _done_cbs: List[Callable[[], None]] = field(default_factory=list)  #: guarded-by _cb_lock
    _cb_lock: threading.Lock = field(default_factory=threading.Lock)

    def add_done_callback(self, cb: Callable[[], None]) -> None:
        """Run ``cb`` when the request completes (immediately if it
        already has) — lets RPC handlers respond event-driven instead of
        parking a handler-pool thread on ``done_event.wait``."""
        with self._cb_lock:
            if not self.done_event.is_set():
                self._done_cbs.append(cb)
                return
        cb()

    def _fire_done(self) -> None:
        with self._cb_lock:
            cbs, self._done_cbs = self._done_cbs, []
        for cb in cbs:
            try:
                cb()
            except Exception:
                pass       # a failing waiter must not kill the step loop


def donates(device=None) -> bool:
    """Whether the engine's steps update the batched cache in place.  On
    an accelerator the cache is most of the device's memory, and a step
    that kept its input would hold it twice.  The CPU backend keeps the
    caller's arrays, so a wrapper of a step may still hand its input
    back."""
    return (device or jax.devices()[0]).platform != "cpu"


def decode_executable(model: Model, impl: str, *, donate: bool):
    """The engine's decode step, jitted as ``jit_decode_step`` (the name
    the device trace finds it under).  ``donate``: the cache (argument 1)
    is consumed and the step writes its new K/V rows into it in place."""
    def decode_step(p, c, t, pos):
        return model.decode_step(p, c, t, pos, impl=impl)
    return jax.jit(decode_step, donate_argnums=(1,) if donate else ())


def sample_executable():
    """The engine's sampler, jitted as ``jit_sample_tokens``: one token
    for each of the rows ``first .. first + len(temperature)`` of
    ``logits`` (its leading dims flattened into rows), the first argmax
    where the row's ``temperature`` is 0, else a draw from
    softmax(logits / T) under a key split inside the call from ``key``.
    Returns the ``(len(temperature),)`` int32 tokens and the next key."""
    def sample_tokens(logits, first, temperature, key):
        logits = jax.lax.dynamic_slice_in_dim(
            logits.reshape(-1, logits.shape[-1]), first, temperature.shape[0])
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        hot = temperature > 0
        key, draw_key = jax.random.split(key)

        def draw():
            t = jnp.where(hot, temperature, 1.0)[:, None]
            drawn = jax.random.categorical(draw_key, logits / t, axis=-1)
            return jnp.where(hot, drawn.astype(jnp.int32), greedy)
        # an all-greedy call draws no noise over the vocabulary
        return jax.lax.cond(jnp.any(hot), draw, lambda: greedy), key
    return jax.jit(sample_tokens)


class ServeEngine:
    """``device`` pins the engine: parameters, the batched cache, the B=1
    staging cache and every jitted output live on that one device, so
    one process can run a replica per chip.  ``None`` keeps JAX's
    default placement."""

    def __init__(self, model: Model, params, *, max_len: int = 512,
                 n_slots: int = 4, seed: int = 0, impl: str = "auto",
                 chunk_tokens: int = 0, session_cap: int = 0,
                 cache_dtype=None, device=None):
        self.model = model
        self.device = device
        self.params = self._put(params)
        self.max_len = max_len
        self.n_slots = n_slots
        self.impl = impl
        self.cache_dtype = cache_dtype or jnp.bfloat16
        self.cache, self.cache_axes = self._zero_cache(n_slots)
        self.pos = np.zeros((n_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.queue: "queue.Queue[Request]" = queue.Queue()
        # set on submit: idle step loops wait on this instead of polling
        self.work = threading.Event()
        self._rng = self._put(jax.random.PRNGKey(seed))
        self._rid = 0  #: guarded-by _lock
        self._lock = threading.Lock()

        # chunked prefill + sessions need continuation-at-an-offset,
        # which only attention-family caches support; fall back silently
        # (stats() exposes the effective configuration)
        chunkable = model.supports_chunked_prefill
        self.chunk = int(chunk_tokens) if (chunk_tokens and chunkable) else 0
        self.session_cap = int(session_cap) if (session_cap
                                                and chunkable) else 0
        # admit-order backlog (step-thread only): requests drained from
        # the thread-safe submit queue but not yet placed in a slot
        self._pending: Deque[Request] = deque()
        # slot -> in-progress chunked-prefill state (step-thread only)
        self._prefill: Dict[int, dict] = {}
        # sid -> {"slot", "tokens", "pos"}; iteration order == LRU
        self.sessions: "OrderedDict[str, dict]" = OrderedDict()
        # session bound to each slot: for an *active* request, the sid it
        # will pin on completion; for a free slot, the pinned session
        self.slot_session: List[Optional[str]] = [None] * n_slots
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.session_evictions = 0
        self.decode_steps = 0
        self.decode_tokens = 0        # sampled from decode steps
        self.prefill_chunks = 0
        self.prefill_tokens = 0       # real (unpadded) tokens of chunks
        # MoE work from shapes alone: routed (token, expert) assignments
        # of real tokens, and the expert-FFN rows the dispatch computes
        self.moe_assignments = 0
        self.moe_rows = 0
        self.token_reads = 0          # device->host copies of tokens
        self._moe_per_token = model.cfg.moe.top_k * model.moe_layers
        self._phase = Phases(_M_PHASE_NS, _M_PHASE_CALLS)

        def prefill(p, b):
            return model.prefill(p, b, cache_len=max_len, impl=impl)

        def prefill_chunk(p, c, t, off):
            return model.prefill_chunk(p, c, t, off, impl=impl)

        # the batched cache is updated in place where the backend donates:
        # every step that takes it (decode, slot scatter) consumes it
        donate = donates(device)
        self._prefill_jit = jax.jit(prefill)
        self._decode_jit = decode_executable(model, impl, donate=donate)
        self._sample_jit = sample_executable()
        if self.chunk or self.session_cap:
            self._chunk_jit = jax.jit(prefill_chunk)
            # zeroed B=1 staging cache, shared template for fresh prompts
            self._cache1_zero, _ = self._zero_cache(1)

        # slot gather/scatter as single jitted executables (slot index is
        # a traced scalar: one compile covers every slot).  Eagerly
        # dispatching one dynamic-slice per cache leaf costs milliseconds
        # per request on the resume path — comparable to the chunk itself
        def gather_slot(cache, slot):
            def one(src, axes):
                return jax.lax.dynamic_slice_in_dim(
                    src, slot, 1, axis=axes.index("batch"))
            return jax.tree_util.tree_map(
                one, cache, self.cache_axes,
                is_leaf=lambda x: hasattr(x, "shape")
                and not isinstance(x, dict))

        def scatter_slot(cache, cache1, slot):
            def one(dst, src, axes):
                return jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), slot,
                    axis=axes.index("batch"))
            return jax.tree_util.tree_map(
                one, cache, cache1, self.cache_axes,
                is_leaf=lambda x: hasattr(x, "shape")
                and not isinstance(x, dict))

        self._gather_jit = jax.jit(gather_slot)
        self._scatter_jit = jax.jit(scatter_slot,
                                    donate_argnums=(0,) if donate else ())

    # -------------------------------------------------------------- placement
    def _put(self, x):
        """Host values and trees onto the engine's device (committed, so
        the jitted steps run there; default placement without one)."""
        return jax.device_put(x, self.device)

    def _zero_cache(self, batch: int):
        """A zeroed (batch, max_len) cache, allocated on the device."""
        with jax.default_device(self.device):
            cache, axes = unzip(self.model.cache_specs(
                batch, self.max_len, dtype=self.cache_dtype))
        return self._put(cache), axes

    # ------------------------------------------------------------------ slots
    def _scatter_slot(self, cache, cache1, slot: int):
        """Insert a B=1 cache into the engine cache at ``slot`` (batch dim
        found via logical axes)."""
        with self._phase("serve.slot_copy"):
            return self._scatter_jit(cache, cache1, np.int32(slot))

    def _gather_slot(self, slot: int):
        """Extract slot ``slot`` of the engine cache as a B=1 cache (the
        staging tree a resumed session's suffix chunks continue into)."""
        with self._phase("serve.slot_copy"):
            return self._gather_jit(self.cache, np.int32(slot))

    def submit(self, prompt, max_new: int = 32, temperature: float = 0.0,
               eos_id: int = -1, frontend=None,
               on_token=None, session_id=None) -> Request:
        prompt = np.asarray(prompt, np.int32)
        span = len(prompt) + (self.model.cfg.frontend_seq
                              if frontend is not None else 0)
        if span + max_new > self.max_len:
            raise ValueError(
                f"prompt span {span} + max_new {max_new} exceeds the "
                f"cache length {self.max_len}")
        if frontend is not None:
            session_id = None       # sessions are token-prefix keyed
        with self._lock:
            self._rid += 1
            rid = self._rid
        req = Request(rid, prompt, max_new,
                      temperature, eos_id, frontend,
                      session_id=session_id, on_token=on_token)
        req.t_submit = time.monotonic()
        self.queue.put(req)
        self.work.set()
        return req

    def pending(self) -> int:
        """Requests submitted but not yet placed in a slot."""
        return self.queue.qsize() + len(self._pending)

    def stats(self) -> Dict[str, Any]:
        busy = sum(1 for r in self.slot_req if r is not None)
        return {"active_slots": busy,
                "n_slots": self.n_slots, "queued": self.pending(),
                "max_len": self.max_len,
                "occupancy": busy / max(self.n_slots, 1),
                "prefilling": len(self._prefill),
                "pinned_sessions": len(self.sessions),
                "session_capacity": self.session_cap,
                "session_evictions": self.session_evictions,
                "chunk_tokens": self.chunk,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "decode_steps": self.decode_steps,
                "decode_tokens": self.decode_tokens,
                "prefill_chunks": self.prefill_chunks,
                "prefill_tokens": self.prefill_tokens,
                "moe_assignments": self.moe_assignments,
                "moe_rows": self.moe_rows,
                "token_reads": self.token_reads,
                "phase_ns": dict(self._phase.ns),
                "phase_calls": dict(self._phase.calls)}

    # ---------------------------------------------------------------- sessions
    def _evict(self, sid: str) -> int:
        """Drop a pinned session; returns the slot it freed."""
        st = self.sessions.pop(sid)
        self.slot_session[st["slot"]] = None
        self.session_evictions += 1
        _M_EVICTIONS.inc()
        return st["slot"]

    def _take_slot(self) -> Optional[int]:
        """A slot for a fresh request: truly free first, else evict the
        LRU pinned session; None when every slot is actively decoding."""
        for i, r in enumerate(self.slot_req):
            if r is None and self.slot_session[i] is None:
                return i
        for sid in list(self.sessions):          # OrderedDict: LRU first
            if self.slot_req[self.sessions[sid]["slot"]] is None:
                return self._evict(sid)
        return None

    def _release_slot(self, slot: int) -> None:
        """Free a finished slot; with sessions enabled and a session id
        bound, the KV stays pinned in the slot under that id."""
        req = self.slot_req[slot]
        self.slot_req[slot] = None
        sid = self.slot_session[slot]
        self.slot_session[slot] = None
        if sid is None or req is None or self.session_cap <= 0:
            return
        # cache holds positions 0..pos-1 = full prompt + all emitted
        # tokens except the last (its K/V was never written)
        tokens = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.out_tokens[:-1], np.int32)])
        if len(tokens) != int(self.pos[slot]):
            return                      # frontend span etc.: not resumable
        old = self.sessions.pop(sid, None)
        if old is not None:
            self.slot_session[old["slot"]] = None
        while len(self.sessions) >= self.session_cap:
            self._evict(next(iter(self.sessions)))
        self.sessions[sid] = {"slot": slot, "tokens": tokens,
                              "pos": int(self.pos[slot])}
        self.slot_session[slot] = sid

    # ------------------------------------------------------------------ admit
    def _admit(self):
        while True:
            try:
                self._pending.append(self.queue.get_nowait())
            except queue.Empty:
                break
        while self._pending:
            req = self._pending[0]
            sid = req.session_id if self.session_cap > 0 else None
            st = self.sessions.get(sid) if sid is not None else None
            if st is not None:
                n = st["pos"]
                if (len(req.prompt) > n
                        and np.array_equal(req.prompt[:n], st["tokens"])):
                    # session hit: resume in the pinned slot, prefill
                    # only the suffix at the cached offset
                    self._pending.popleft()
                    slot = st["slot"]
                    self.sessions.pop(sid)       # re-pinned on completion
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += n
                    _M_PREFIX_HITS.inc()
                    _M_TOKENS_SAVED.inc(n)
                    req.t_admit = time.monotonic()
                    self.slot_req[slot] = req
                    self.slot_session[slot] = sid
                    self._start_chunked(slot, req, req.prompt[n:], base=n,
                                        cache1=self._gather_slot(slot))
                    continue
                # stale prefix: the cached KV is useless for this prompt
                self._evict(sid)
            if sid is not None:
                self.prefix_misses += 1
                _M_PREFIX_MISSES.inc()
            slot = self._take_slot()
            if slot is None:
                return                   # every slot actively decoding
            self._pending.popleft()
            req.t_admit = time.monotonic()
            self.slot_req[slot] = req
            self.slot_session[slot] = sid
            if self.chunk and req.frontend is None:
                self._start_chunked(slot, req, req.prompt, base=0,
                                    cache1=self._cache1_zero)
            else:
                self._prefill_monolithic(slot, req)

    def _prefill_monolithic(self, slot: int, req: Request):
        batch = {"tokens": self._put(req.prompt[None, :])}
        if req.frontend is not None:
            batch["frontend"] = self._put(req.frontend[None])
        logits, cache1 = self._prefill_jit(self.params, batch)
        prompt_span = len(req.prompt) + (
            self.model.cfg.frontend_seq if req.frontend is not None else 0)
        self._count_moe(prompt_span, prompt_span)
        self.cache = self._scatter_slot(self.cache, cache1, slot)
        with self._phase("serve.sample"):
            tok = int(self._sample(logits, [req])[0])
            self.pos[slot] = prompt_span
            self.last_tok[slot] = tok
            self._emit(req, tok)
        if req.done_event.is_set():
            self._release_slot(slot)

    # ---------------------------------------------------------------- chunked
    def _start_chunked(self, slot: int, req: Request, suffix, *, base: int,
                       cache1):
        """Queue a chunked prefill: ``suffix`` tokens land at absolute
        positions ``base..`` of the B=1 staging cache, one chunk per
        step().  Padded to the fixed chunk size so any prompt length
        reuses one jit compile (padded K/V sit above the live position;
        decode overwrites them before they become visible)."""
        C = self.chunk or _RESUME_CHUNK
        toks = np.asarray(suffix, np.int32)
        n = len(toks)
        pad = (-n) % C
        if pad:
            toks = np.concatenate([toks, np.zeros(pad, np.int32)])
        self._prefill[slot] = {"req": req, "cache1": cache1, "toks": toks,
                               "n": n, "off": 0, "base": base}

    def _prefill_step(self, slot: int, st: dict):
        """Advance one chunk; on the final chunk, scatter the staged
        cache into the slot and emit the first sampled token."""
        C = self.chunk or _RESUME_CHUNK
        req = st["req"]
        with self._phase("serve.prefill_chunk"):
            chunk = self._put(st["toks"][st["off"]:st["off"] + C][None, :])
            off = st["base"] + st["off"]
            logits, st["cache1"] = self._chunk_jit(
                self.params, st["cache1"], chunk, np.int32(off))
        real = min(C, st["n"] - st["off"])
        self.prefill_chunks += 1
        self.prefill_tokens += real
        _M_PREFILL_CHUNKS.inc()
        _M_PREFILL_TOKENS.inc(real)
        self._count_moe(real, C)
        st["off"] += C
        if st["off"] < st["n"]:
            return
        # prefill complete
        del self._prefill[slot]
        last = st["n"] - 1 - (st["off"] - C)   # last real token, this chunk
        self.cache = self._scatter_slot(self.cache, st["cache1"], slot)
        self.pos[slot] = st["base"] + st["n"]
        with self._phase("serve.sample"):
            tok = int(self._sample((logits, last), [req])[0])
            self.last_tok[slot] = tok
            self._emit(req, tok)
        if req.done_event.is_set():
            self._release_slot(slot)

    def _sample(self, logits, reqs) -> np.ndarray:
        """The tokens of one call's ``logits`` (leading dims flattened
        into rows), one per entry of ``reqs``, which lines up with the
        rows from 0, or from ``row`` where ``logits`` is a pair
        ``(logits, row)`` (None: a row nobody reads, sampled greedily):
        one sampler dispatch, then one device->host copy of the tokens."""
        logits, first = logits if isinstance(logits, tuple) else (logits, 0)
        temps = np.asarray([0.0 if r is None else r.temperature
                            for r in reqs], np.float32)
        toks, self._rng = self._sample_jit(logits, np.int32(first), temps,
                                           self._rng)
        self.token_reads += 1
        _M_TOKEN_READS.inc()
        return jax.device_get(toks)

    def _emit(self, req: Request, tok: int):
        if not req.out_tokens:
            req.t_first = time.monotonic()
        req.out_tokens.append(tok)
        if req.on_token:
            req.on_token(req.rid, tok)
        if tok == req.eos_id or len(req.out_tokens) >= req.max_new:
            req.done_event.set()
            req._fire_done()

    # ------------------------------------------------------------------ step
    def step(self) -> int:
        """One engine step: admit, advance one prefill chunk per
        prefilling slot, one decode step for all decoding slots; returns
        #occupied slots (decoding + mid-prefill)."""
        with self._phase("serve.step"):
            with self._phase("serve.admit"):
                self._admit()
            for slot in list(self._prefill):
                self._prefill_step(slot, self._prefill[slot])
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None and i not in self._prefill]
            if active:
                self._decode(active)
            return sum(1 for r in self.slot_req if r is not None)

    def _decode(self, active: List[int]) -> None:
        """One decode step for the ``active`` slots, then one sampler call
        over every slot's row and an emit of each active slot's token."""
        with self._phase("serve.decode"):
            # copies: the host arrays advance while the step may still
            # be reading its inputs
            toks = self._put(self.last_tok[:, None].copy())
            pos = self._put(self.pos.copy())
            logits, self.cache = self._decode_jit(self.params, self.cache,
                                                  toks, pos)
            # the sampler's token read would block on these logits
            # anyway; waiting here keeps the device's decode inside
            # serve.decode and only the sampler and the per-slot emit in
            # serve.sample
            jax.block_until_ready(logits)
        sampled = 0
        with self._phase("serve.sample"):
            rows: List[Optional[Request]] = [None] * self.n_slots
            for i in active:
                rows[i] = self.slot_req[i]
            tokens = self._sample(logits, rows).tolist()
            for i in active:
                req = self.slot_req[i]
                if req.done_event.is_set():
                    self._release_slot(i)
                    continue
                tok = tokens[i]
                sampled += 1
                self.pos[i] += 1
                self.last_tok[i] = tok
                self._emit(req, tok)
                if req.done_event.is_set():
                    self._release_slot(i)
        # counted together, after the step, so that a stats() snapshot
        # taken from another thread sees whole steps
        self.decode_steps += 1
        self.decode_tokens += sampled
        _M_DECODE_STEPS.inc()
        _M_DECODE_TOKENS.inc(sampled)
        self._count_moe(len(active), self.n_slots)

    def _count_moe(self, real: int, tokens: int) -> None:
        """The MoE work of one call over ``tokens`` tokens, ``real`` of
        them a request's: their routed assignments, and the expert rows
        the model's dispatch computes for a call of that size."""
        if not self._moe_per_token:
            return
        n, rows = real * self._moe_per_token, self.model.moe_rows(tokens)
        self.moe_assignments += n
        self.moe_rows += rows
        _M_MOE_ASSIGNMENTS.inc(n)
        _M_MOE_ROWS.inc(rows)

    def fail_all(self, reason: str) -> int:
        """Fail every request the engine holds — decoding, mid-prefill,
        pending or queued — with ``reason``, and drop the pinned
        sessions (a step that raised may have left any slot's KV half
        written, or the whole cache donated).  Step-thread only.  Returns
        how many were failed."""
        reqs = [r for r in self.slot_req if r is not None]
        reqs += list(self._pending)
        while True:
            try:
                reqs.append(self.queue.get_nowait())
            except queue.Empty:
                break
        self._pending.clear()
        self._prefill.clear()
        # a step that raised after its dispatch donated the cache left it
        # deleted: start again from a zeroed one
        if any(x.is_deleted() for x in jax.tree_util.tree_leaves(self.cache)):
            self.cache, _ = self._zero_cache(self.n_slots)
        self.slot_req = [None] * self.n_slots
        self.slot_session = [None] * self.n_slots
        self.sessions.clear()
        self.pos[:] = 0
        self.last_tok[:] = 0
        for req in reqs:
            req.error = reason
            req.done_event.set()
            req._fire_done()
        return len(reqs)

    def drain(self):
        """Run steps until queue and slots are empty (pinned sessions
        hold no slot_req and do not block draining)."""
        while True:
            n = self.step()
            if n == 0 and self.pending() == 0:
                return

    def generate(self, prompts, max_new: int = 32, temperature: float = 0.0,
                 eos_id: int = -1, frontends=None,
                 session_ids=None) -> List[List[int]]:
        reqs = [self.submit(p, max_new, temperature, eos_id,
                            None if frontends is None else frontends[i],
                            session_id=(None if session_ids is None
                                        else session_ids[i]))
                for i, p in enumerate(prompts)]
        self.drain()
        return [r.out_tokens for r in reqs]
