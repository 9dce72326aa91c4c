"""StreamServer: many logical sensor streams, one compiled step per chunk.

Slot model: the server owns a slot-batched ``SessionState`` with fixed
capacity S. ``open()`` pins a session to a free slot (evicting the
least-recently-fed idle session to the checkpoint store when full),
``feed()`` absorbs chunks for any subset of resident sessions in ONE jitted
donated-state call per chunk bucket, and ``close()``/``evict()`` release the
slot — an evicted session's DSP registers and decision history are parked in
the named-checkpoint store, so reopening resumes bit-exactly.

Retrace bounding: arbitrary packet lengths are padded up to the next power
of two (clamped to ``[min_chunk, max_chunk]``; longer packets split), so at
most O(log max_chunk) step variants ever compile, no matter what lengths
sensors send.

Async feed pipeline: ``feed()`` is a synchronous wrapper over a pipelined
hot path — ``submit()`` validates and enqueues requests (optionally
dispatching on a coalescing watermark/deadline), dispatch stages each wave
into one of two pre-allocated host buffers per bucket (slot-targeted
clears, reuse gated on the wave that last read the buffer) and launches
the donated step WITHOUT reading decisions back, and ``drain()`` is the
only host-device sync point: it blocks once, vectorizes the decision
readback, and resolves every outstanding ``FeedTicket``. Many callers'
small submits coalesce into one compiled call per wave instead of one
full-capacity step each. Decisions are bit-for-bit what the synchronous
path returns — ``feed()`` IS ``submit()`` + ``drain()``.

Instrumentation: every public call and every stage of a wave records a
``jax.profiler.TraceAnnotation`` (``serve.*``; a no-op check when no
profiler trace is active), one per operation and never one per request,
carrying the wave's step number, bucket or slot as metadata; and
``stats()`` carries plain counters of the work done (readbacks, staging
waits, host-to-device bytes, padding, slot resets, compiles inside
server calls). ``docs/serving.md`` lists both.

Scale-out: pass ``mesh=`` to shard the slot axis over the mesh's data axes
(see ``repro.distributed.sharding.session_specs``); capacity then scales
linearly with device count while the host-side API is unchanged. For
host-side sharding — N servers behind one admission API — see
``repro.serving.router.StreamRouter``.
"""

from __future__ import annotations

import functools
import time
from contextlib import contextmanager
from typing import Iterable, List, Optional, Union

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import pipeline as pl
from repro.core.pipeline import InFilterPipeline, SessionState
from repro.serving.session import (Decision, FeedRequest, FeedResult,
                                   FeedTicket, Session)

__all__ = ["StreamServer", "bucket_length", "make_batched_step", "COUNTERS",
           "COMPILE_SITES"]

# plain work counters of ``StreamServer.stats()`` (the router sums them)
COUNTERS = ("drains", "readbacks", "stage_waits", "h2d_bytes",
            "valid_samples", "padded_samples", "slot_resets")
# where ``stats()["compiles"]`` / ``["cache_loads"]`` happened: the step
# launch, or the slot updates of open/close/evict
COMPILE_SITES = ("launch", "lifecycle")


@functools.cache
def _reset_program(layout=None):
    """The lifecycle program: ``pl.reset_slot`` on the donated state. The
    slot and the flag are traced, so one executable serves every slot, open
    and close alike. Under a mesh, ``layout`` is the state leaves'
    shardings, which the outputs keep: the next step neither reshards nor
    recompiles."""
    kw = {} if layout is None else {"out_shardings": layout}
    return jax.jit(pl.reset_slot, donate_argnums=(0,), **kw)

# Process-wide compile accounting. JAX reports every backend compile,
# persistent-cache loads included, as one backend-compile event, and a
# persistent-cache hit as an event of its own inside it; a server charges
# the change across its own calls to itself.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_jax_compiles = {"backend": 0, "cache_hits": 0}


def _on_compile(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        _jax_compiles["backend"] += 1


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        _jax_compiles["cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile)
jax.monitoring.register_event_listener(_on_event)


def bucket_length(n: int, min_chunk: int, max_chunk: int) -> int:
    """Next power of two >= n, clamped to [min_chunk, max_chunk]."""
    if n <= 0:
        raise ValueError(f"chunk length must be positive, got {n}")
    b = min_chunk
    while b < n:
        b <<= 1
    return min(b, max_chunk)


def _batched_step(pipe: InFilterPipeline, state: SessionState,
                  chunk: jax.Array, valid: jax.Array):
    state, p, _ = pipe._session_step(state, chunk, valid)
    return state, p


def make_batched_step(pipeline: InFilterPipeline, mesh=None):
    """Compile the donated-state session step for ``pipeline``.

    Returns a callable ``(pipe, state, chunk, valid) -> (state, p)`` with a
    uniform signature across numerics modes; its ``lower`` takes the same
    arguments and gives the AOT-lowered step (for its compiled text). A
    ``StreamServer`` builds one per instance by default; pass the SAME
    callable to several servers (``step_fn=``) to share one compile cache
    across shards — the ``StreamRouter`` does exactly that, so N shards
    cost one compile per chunk bucket, not N.

    With ``mesh`` the step runs under ``shard_map`` over the slot axis
    (the mesh's data axes): every op of the step is row-parallel, so each
    device advances its own slots — Pallas kernels included — with no
    collective, and the results are bitwise those of one device.
    """
    if pipeline.config.numerics == "fixed":
        # the integer program lowers HOST-side (concrete ROMs/shift
        # tables), so the pipeline cannot ride along as a traced pytree
        # argument the way the float step's weights do. Precompile once
        # and jit a closure over the concrete pipeline: the step's only
        # traced inputs are the donated integer registers + the chunk.
        pipeline.fixed_program()
        body = lambda state, chunk, valid: _batched_step(
            pipeline, state, chunk, valid)
        fixed_step = jax.jit(_slot_parallel(body, mesh, 0),
                             donate_argnums=(0,))

        def step(pipe, state, chunk, valid):
            return fixed_step(state, chunk, valid)

        step.lower = lambda pipe, state, chunk, valid: fixed_step.lower(
            state, chunk, valid)
        return step
    return jax.jit(_slot_parallel(_batched_step, mesh, 1),
                   donate_argnums=(1,))


def _slot_parallel(fn, mesh, n_replicated: int):
    """``fn`` under ``shard_map`` over the slot axis of ``mesh``: its first
    ``n_replicated`` arguments are replicated, and every leaf of the other
    arguments and of the outputs leads with the slot axis. No mesh: ``fn``."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import data_axes
    slots = P(data_axes(mesh))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(),) * n_replicated + (slots,) * 3,
                         out_specs=slots, check_vma=False)


class _StageBuffer:
    """One host-side staging buffer of a per-bucket double-buffer pair.

    ``inflight`` holds the decision array of the last wave staged from this
    buffer: blocking on it before reuse proves the donated step that read
    the buffer has fully executed, so rewriting the rows is safe even if
    the host->device transfer was zero-copy. Two buffers per bucket give
    the classic depth-2 pipeline: stage wave k+1 while the device still
    chews on wave k.
    """

    __slots__ = ("batch", "valid", "dirty", "inflight")

    def __init__(self, capacity: int, length: int, dtype):
        self.batch = np.zeros((capacity, length), dtype)
        self.valid = np.zeros((capacity,), np.int32)
        self.dirty: list = []          # slots written by the last wave
        self.inflight = None           # that wave's decision array


class _Pending:
    """One submitted request riding the coalescing queue."""

    __slots__ = ("ticket", "pos", "sid", "segs", "total", "label", "conf")

    def __init__(self, ticket, pos, sid, segs, total):
        self.ticket = ticket
        self.pos = pos                 # index within the ticket
        self.sid = sid
        self.segs = segs               # max_chunk-bounded segments
        self.total = total             # original chunk length in samples
        self.label = None
        self.conf = None


class StreamServer:
    """Multiplex logical sensor streams onto fixed slot capacity.

    Parameters
    ----------
    pipeline:       the deployable ``InFilterPipeline``. Its config's
                    ``stream_impl`` picks the donated batch step's hot path
                    ("xla" or the stateful "pallas" streaming kernel —
                    bit-identical decisions either way). Its
                    ``numerics`` picks the engine: "float" (f32 registers)
                    or "fixed" — the bit-true int32 hardware twin, whose
                    streamed decisions are bit-for-bit equal to one-shot
                    ``pipeline.apply(x)`` under any chunking and under
                    EITHER stream_impl (the int Pallas kernel matches the
                    int XLA step register-for-register;
                    ``stats()["numerics"]`` reports the live mode).
    capacity:       number of slots S (streams resident at once).
    max_chunk:      largest per-call chunk; longer packets are split.
                    Must be a power of two (validated at construction).
    min_chunk:      smallest pad bucket (tiny packets share one variant).
                    Must be a power of two — the bucket ladder doubles
                    from ``min_chunk`` to ``max_chunk``, giving at most
                    ``log2(max_chunk / min_chunk) + 1`` compiled variants.
    dtype:          register/sample dtype; incoming chunks are cast to it
                    explicitly (the session dtype never drifts mid-stream).
    evict_after:    seconds of idleness before a resident session may be
                    auto-evicted to make room; ``None`` = any idle session.
    checkpoint_dir: where evicted sessions are parked; required for
                    eviction/reopen (without it a full server raises).
    mesh:           optional ``jax.sharding.Mesh`` (Auto axes) — shard the
                    slot axis over the mesh's data axes; ``capacity`` must
                    be a multiple of their size. A shared ``step_fn`` must
                    be built with the same mesh.
    clock:          injectable monotonic clock (tests).
    coalesce_watermark: auto-dispatch threshold for the async queue: once
                    this many requests are pending, ``submit()`` launches
                    the waves (staging + donated step, NO readback — the
                    host never blocks). ``None`` (default) dispatches only
                    at ``drain()``/deadline.
    coalesce_deadline: max seconds a queued request may wait before the
                    next ``submit()``/``poll()`` dispatches the queue.
                    Checked cooperatively on API calls — there is no
                    background thread.
    step_fn:        a compiled step from :func:`make_batched_step` built
                    for this same pipeline — pass one callable to several
                    servers to share its compile cache (the router's N
                    shards compile each chunk bucket once, not N times).
    """

    def __init__(self, pipeline: InFilterPipeline, capacity: int = 64, *,
                 max_chunk: int = 4096, min_chunk: int = 16,
                 dtype=jnp.float32, evict_after: Optional[float] = None,
                 checkpoint_dir: Optional[str] = None, mesh=None,
                 max_history: int = 64, clock=None,
                 coalesce_watermark: Optional[int] = None,
                 coalesce_deadline: Optional[float] = None,
                 step_fn=None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if not (0 < min_chunk <= max_chunk):
            raise ValueError("need 0 < min_chunk <= max_chunk")
        # BOTH bounds must be powers of two: bucket_length doubles up from
        # min_chunk, so a non-pow2 min makes every bucket non-pow2 (novel
        # compiled variants per length) and a non-pow2 max clamps the top
        # bucket off the pow2 grid — either way the O(log max/min) retrace
        # bound quietly stops holding. Fail at construction, not after the
        # compile cache has already ballooned.
        for bname, v in (("min_chunk", min_chunk), ("max_chunk", max_chunk)):
            if v & (v - 1):
                raise ValueError(
                    f"{bname} must be a power of two, got {v} (the pad-"
                    "bucket ladder doubles from min_chunk to max_chunk)")
        # fail at construction, not on the first feed(): the Pallas
        # streaming kernel has no MAC-mode variant
        if pipeline.config.stream_impl == "pallas" \
                and pipeline.config.mode != "mp":
            raise ValueError(
                "stream_impl='pallas' requires an MP-mode pipeline "
                f"(got mode={pipeline.config.mode!r})")
        self.pipeline = pipeline
        self.capacity = capacity
        self.max_chunk = max_chunk
        self.min_chunk = min_chunk
        self.dtype = jnp.dtype(dtype)
        self.evict_after = evict_after
        self._clock = clock if clock is not None else time.monotonic
        self._mesh = mesh
        self._state = pipeline.init_session(
            capacity, dtype, active=np.zeros((capacity,), bool))
        self._chunk_sharding = None
        self._valid_sharding = None
        if mesh is not None:
            from repro.distributed import sharding as sh
            n_dp = int(np.prod([mesh.shape[a] for a in sh.data_axes(mesh)]))
            if capacity % n_dp:
                raise ValueError(
                    f"capacity {capacity} must be a multiple of the mesh's "
                    f"data-parallel size {n_dp}: each device holds an equal "
                    "share of the slots")
            self._state = sh.shard_session(self._state, mesh)
            dp = sh.data_axes(mesh)
            self._chunk_sharding = jax.sharding.NamedSharding(
                mesh, sh.sanitize((dp, None), (capacity, max_chunk), mesh))
            self._valid_sharding = jax.sharding.NamedSharding(
                mesh, sh.sanitize((dp,), (capacity,), mesh))
        self._step = step_fn if step_fn is not None \
            else make_batched_step(pipeline, mesh)
        self._free = list(range(capacity - 1, -1, -1))  # pop() -> slot 0 first
        self._sessions: dict[str, Session] = {}
        self._manager = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointManager
            self._manager = CheckpointManager(checkpoint_dir,
                                              async_save=False)
        self._max_history = max_history
        self.bucket_counts: dict[int, int] = {}  # bucket length -> steps run
        self.steps_run = 0
        self._count = dict.fromkeys(COUNTERS, 0)
        self._compiles = dict.fromkeys(COMPILE_SITES, 0)
        self._cache_loads = dict.fromkeys(COMPILE_SITES, 0)
        # set when a donated step call raised mid-feed: the failed call
        # consumed the slot-batched state's buffers, so every resident
        # session's registers are gone — the description names the wave
        self._poisoned: Optional[str] = None
        # -- async feed pipeline state --
        self.coalesce_watermark = coalesce_watermark
        self.coalesce_deadline = coalesce_deadline
        self._staging: dict[int, list] = {}   # bucket L -> [_StageBuffer]*2
        self._stage_flip: dict[int, int] = {}
        self._queue: List[_Pending] = []      # submitted, not yet dispatched
        self._queue_since: Optional[float] = None
        self._dispatched: List[_Pending] = []  # dispatched, not yet resolved
        # per dispatched wave with at least one finishing request:
        # (its step number, decision device array, [(pending, slot), ...])
        self._inflight: list = []

    # -- introspection -------------------------------------------------------

    @property
    def state(self) -> SessionState:
        return self._state

    def session(self, session_id: str) -> Session:
        try:
            return self._sessions[session_id]
        except KeyError:
            raise KeyError(f"session {session_id!r} is not open") from None

    def sessions(self) -> list:
        return sorted(self._sessions.values(), key=lambda s: s.slot)

    def is_open(self, session_id: str) -> bool:
        return session_id in self._sessions

    def __contains__(self, session_id: str) -> bool:
        return session_id in self._sessions

    def stats(self) -> dict:
        total = sum(self.bucket_counts.values())
        return {
            "capacity": self.capacity,
            "resident": len(self._sessions),
            "free_slots": len(self._free),
            "steps_run": self.steps_run,
            "stream_impl": self.pipeline.config.stream_impl,
            # operators must be able to tell a fixed-point deployment
            # preview from the float path at a glance
            "numerics": self.pipeline.config.numerics,
            "buckets": dict(sorted(self.bucket_counts.items())),
            # which pad buckets actually absorb the traffic — a ladder rung
            # with a high hit rate and a lot of padding is a resize lever
            "bucket_steps_total": total,
            "bucket_hit_rate": {L: round(c / total, 4) for L, c in
                                sorted(self.bucket_counts.items())}
            if total else {},
            # a poisoned server must be visible from monitoring, not only
            # from the next call's RuntimeError: None = healthy, else the
            # diagnosis string naming the failed wave
            "poisoned": self._poisoned,
            # async feed pipeline depth
            "queued_requests": len(self._queue),
            "unresolved_requests": len(self._dispatched),
            "inflight_waves": len(self._inflight),
            "coalesce_watermark": self.coalesce_watermark,
            "coalesce_deadline": self.coalesce_deadline,
            # work counters (docs/serving.md): drain() calls, blocking
            # decision readbacks, waits for a staging buffer's last wave,
            # bytes staged to the device, valid samples staged and the
            # slots x bucket area they were padded into, lifecycle
            # programs launched outside the step, and backend compiles /
            # persistent-cache loads inside this server's calls, by site
            **self._count,
            "compiles": dict(self._compiles),
            "cache_loads": dict(self._cache_loads),
        }

    # -- instrumentation -----------------------------------------------------

    @contextmanager
    def _compiling(self, site: str):
        """Charge the backend compiles and persistent-cache loads inside
        the block to ``site`` (one of ``COMPILE_SITES``)."""
        c0, h0 = _jax_compiles["backend"], _jax_compiles["cache_hits"]
        try:
            yield
        finally:
            hits = _jax_compiles["cache_hits"] - h0
            self._compiles[site] += _jax_compiles["backend"] - c0 - hits
            self._cache_loads[site] += hits

    @contextmanager
    def _lifecycle(self, span: str, slot: int):
        """A lifecycle span around state work on ``slot``."""
        with TraceAnnotation(span, slot=slot), self._compiling("lifecycle"):
            yield

    def _reset_slot(self, slot: int, active: bool) -> None:
        """Zero ``slot``'s registers and write its admission flag: one
        launch of the donated lifecycle program, counted as a slot reset."""
        reset = _reset_program() if self._mesh is None else _reset_program(
            jax.tree.map(lambda a: a.sharding, self._state))
        self._state = reset(self._state, np.int32(slot), np.bool_(active))
        self._count["slot_resets"] += 1

    # -- admission -----------------------------------------------------------

    def open(self, session_id: str) -> Session:
        """Admit a stream. If a checkpoint for this id exists (prior
        eviction), the session resumes from it bit-exactly; otherwise the
        slot starts from the cleared-register state. Holds for BOTH
        numerics modes — an evicted fixed-mode session's integer registers
        round-trip the named-checkpoint store losslessly (dtype-checked),
        so a reopened int32 stream continues bit-for-bit."""
        with TraceAnnotation("serve.open") as span:
            self._check_poisoned()
            # flush the async queue first: admission may evict the LRU
            # session, and the victim choice / parked registers must
            # reflect every feed submitted so far (exactly as if they had
            # been synchronous)
            self._flush_pending()
            if session_id in self._sessions:
                raise ValueError(f"session {session_id!r} already open")
            # validate at admission (checkpoint-name charset), BEFORE any
            # state changes — a bad id must not cost a slot or surface
            # mid-lifecycle
            if not session_id or not all(ch.isalnum() or ch in "-_."
                                         for ch in session_id):
                raise ValueError(
                    f"session id {session_id!r}: use [A-Za-z0-9._-]")
            slot = self._acquire_slot()
            span.set_metadata(slot=slot)
            try:
                now = self._clock()
                sess = Session(id=session_id, slot=slot, opened_at=now,
                               last_fed=now, max_history=self._max_history)
                name = self._ckpt_name(session_id)
                restore = self._manager is not None \
                    and self._manager.has_named(name)
                # a restored slot is admitted by its row's write, so a
                # restore that fails leaves the slot cleared and inactive
                with self._lifecycle("serve.slot_reset", slot):
                    self._reset_slot(slot, not restore)
                if restore:
                    with self._lifecycle("serve.restore", slot):
                        row_like = pl.take_slot(self._state, slot)
                        row, meta = self._manager.restore_named(name,
                                                                row_like)
                        self._state = pl.put_slot(
                            self._state, slot, row._replace(active=True))
                        self._count["slot_resets"] += 1
                    if meta:
                        sess.load_meta(meta)
            except Exception:
                self._free.append(slot)  # a failed admission keeps no slot
                raise
            self._sessions[session_id] = sess
            return sess

    def close(self, session_id: str, *, checkpoint: bool = False) -> Session:
        """Release a session's slot. ``checkpoint=True`` parks its state
        (float or integer registers alike) for a later ``open`` (same as
        eviction); otherwise any parked copy is discarded — a future
        ``open`` of this id starts fresh."""
        with TraceAnnotation("serve.close") as span:
            # absorb + resolve any queued feeds for this session before its
            # registers are parked/discarded — closing must not drop
            # submitted chunks (the sync path can't, so the async path may
            # not either)
            self._flush_pending()
            if session_id not in self._sessions:
                raise KeyError(f"session {session_id!r} is not open")
            sess = self._sessions.pop(session_id)
            span.set_metadata(slot=sess.slot)
            if checkpoint:
                with self._lifecycle("serve.park", sess.slot):
                    self._park(sess)
            elif self._manager is not None:
                self._manager.delete_named(self._ckpt_name(session_id))
            # the registers are parked or discarded by now, and no step
            # reads an inactive slot: clearing them with the flag is free
            with self._lifecycle("serve.slot_reset", sess.slot):
                self._reset_slot(sess.slot, False)
            self._free.append(sess.slot)
            return sess

    def evict(self, session_id: str) -> Session:
        """Park a resident session in the checkpoint store and free its
        slot. Requires ``checkpoint_dir``. An unknown id is reported as
        such (the same ``KeyError`` shape every lookup raises) BEFORE the
        checkpoint-manager check — "no checkpoint_dir" for a session that
        isn't even resident was a misdiagnosis."""
        if session_id not in self._sessions:
            raise KeyError(f"session {session_id!r} is not open")
        if self._manager is None:
            raise RuntimeError("evict() needs checkpoint_dir")
        return self.close(session_id, checkpoint=True)

    def _park(self, sess: Session) -> None:
        if self._manager is None:
            raise RuntimeError("session checkpointing needs checkpoint_dir")
        row = pl.take_slot(self._state, sess.slot)
        self._manager.save_named(self._ckpt_name(sess.id), row,
                                 meta=sess.meta())

    def _check_poisoned(self) -> None:
        if self._poisoned is not None:
            raise RuntimeError(
                f"server is poisoned: {self._poisoned}. The failed step "
                "consumed the donated slot-batched state, so every "
                "resident session's registers are unrecoverable — build "
                "a new StreamServer and reopen sessions from their "
                "checkpoints")

    @staticmethod
    def _ckpt_name(session_id: str) -> str:
        return f"session-{session_id}"

    def _acquire_slot(self) -> int:
        if self._free:
            return self._free.pop()
        if self._manager is None:
            raise RuntimeError(
                f"server at capacity ({self.capacity}) and no "
                "checkpoint_dir to evict into")
        now = self._clock()
        lru = min(self._sessions.values(), key=lambda s: s.last_fed)
        if self.evict_after is not None and \
                now - lru.last_fed < self.evict_after:
            raise RuntimeError(
                f"server at capacity ({self.capacity}); least-recent "
                f"session {lru.id!r} idle {now - lru.last_fed:.1f}s < "
                f"evict_after={self.evict_after}s")
        self.evict(lru.id)
        return self._free.pop()

    # -- the hot path --------------------------------------------------------

    def feed(self, requests: Iterable[Union[FeedRequest, tuple]]) -> list:
        """Absorb one chunk per request; return one ``FeedResult`` per
        request, in request order.

        Each request is a ``FeedRequest`` or ``(session_id, chunk)`` with a
        1-D chunk. Chunks longer than ``max_chunk`` are split; several
        requests for the SAME session in one call are applied in order.
        Everything that can share a compiled call does: per wave, all
        pending segments are padded into one (S, L_bucket) batch with
        per-slot valid counts, and absent/inactive slots ride along inertly.

        Chunks are always float audio regardless of numerics: a fixed-mode
        server quantizes onto its static ADC grid inside the step, and its
        decisions equal one-shot inference on the concatenated audio
        bit-for-bit (a float server matches to f32 round-off, bit-for-bit
        under ``quant_bits`` once the running amax has seen the peak).

        This is the synchronous wrapper over the async pipeline: exactly
        ``submit(requests)`` + ``drain()`` — same staging buffers, same
        waves, same readback — so its decisions are bit-for-bit identical
        to the ``submit``/``poll``/``drain`` path by construction. Any
        requests already queued by earlier ``submit()`` calls are flushed
        (in their submit order) by the same drain.
        """
        ticket = self.submit(requests)
        self.drain()
        return ticket.results

    def feed_async(self,
                   requests: Iterable[Union[FeedRequest, tuple]]
                   ) -> FeedTicket:
        """Alias of :meth:`submit` — the asynchronous ``feed()``."""
        return self.submit(requests)

    def submit(self,
               requests: Iterable[Union[FeedRequest, tuple]]) -> FeedTicket:
        """Enqueue one chunk per request; return a ``FeedTicket`` that
        resolves at the next drain point.

        Validation is atomic: every request is checked (open session, 1-D
        non-empty chunk) BEFORE any is enqueued, so a bad batch never
        half-submits. Requests accumulate across callers — per session
        FIFO, across sessions coalesced — and dispatch (staging + donated
        step launch, no readback) happens when ``coalesce_watermark``
        requests are pending, when a queued request is older than
        ``coalesce_deadline``, or at the latest inside ``drain()``.
        """
        self._check_poisoned()
        with TraceAnnotation("serve.submit") as span:
            entries = []
            for r in requests:
                if isinstance(r, FeedRequest):
                    sid, chunk = r.session_id, r.chunk
                else:
                    sid, chunk = r
                if sid not in self._sessions:
                    raise KeyError(f"session {sid!r} is not open")
                chunk = np.asarray(chunk, dtype=self.dtype)
                if chunk.ndim != 1:
                    raise ValueError(
                        f"chunk for {sid!r} must be 1-D (samples,), got "
                        f"shape {chunk.shape}")
                if chunk.shape[0] == 0:
                    raise ValueError(f"empty chunk for session {sid!r}")
                segs = [chunk[i:i + self.max_chunk]
                        for i in range(0, chunk.shape[0], self.max_chunk)]
                entries.append((sid, segs, chunk.shape[0]))
            span.set_metadata(requests=len(entries))
            ticket = FeedTicket(n_requests=len(entries))
            if not entries:
                ticket.results = []
                return ticket
            for pos, (sid, segs, total) in enumerate(entries):
                self._queue.append(_Pending(ticket, pos, sid, segs, total))
            if self._queue_since is None:
                self._queue_since = self._clock()
        if self.coalesce_watermark is not None \
                and len(self._queue) >= self.coalesce_watermark:
            self._dispatch()
        elif self._deadline_expired():
            self._dispatch()
        return ticket

    def poll(self, ticket: FeedTicket) -> Optional[list]:
        """Non-blocking progress check: the ticket's results if they are
        ready, else ``None``.

        "Ready" means every wave carrying one of the ticket's final
        segments has finished on device — ``poll`` never waits for the
        device, but it does advance the pipeline cooperatively: it
        dispatches the queue when the coalescing deadline has expired, and
        it resolves finished waves (a cheap readback of already-computed
        decisions). Use ``drain()`` to block until resolution instead.
        """
        if ticket.done:
            return ticket.results
        self._check_poisoned()
        if self._deadline_expired():
            self._dispatch()
        if self._inflight and all(
                p.is_ready() for _, p, _ in self._inflight):
            self._resolve()
        return ticket.results if ticket.done else None

    def drain(self) -> list:
        """The pipeline's sync point: dispatch everything still queued,
        block until the device has produced every outstanding decision,
        and resolve all open tickets. Returns the ``FeedResult``s resolved
        by THIS drain, in submit order. A drained server has no queued
        requests, no unresolved tickets, and no in-flight waves."""
        self._check_poisoned()
        self._count["drains"] += 1
        self._dispatch()
        return self._resolve()

    def _deadline_expired(self) -> bool:
        return (self.coalesce_deadline is not None
                and self._queue_since is not None
                and self._clock() - self._queue_since
                >= self.coalesce_deadline)

    def _flush_pending(self) -> None:
        """Absorb + resolve everything outstanding before a lifecycle
        mutation (open/close/evict). No-op on a poisoned server — the
        queue is as dead as the registers, and the lifecycle call's own
        poison check owns the error."""
        if self._poisoned is not None:
            return
        if self._queue or self._dispatched or self._inflight:
            with TraceAnnotation("serve.flush"):
                self._dispatch()
                self._resolve()

    def _stage_buffer(self, L: int) -> _StageBuffer:
        """Flip to the next staging buffer for bucket ``L``, waiting (only
        if the device is >= 2 waves behind) for the wave that last read it,
        then clearing exactly the slots that wave wrote."""
        ring = self._staging.get(L)
        if ring is None:
            ring = self._staging[L] = [
                _StageBuffer(self.capacity, L, self.dtype) for _ in range(2)]
            self._stage_flip[L] = 0
        k = self._stage_flip[L]
        self._stage_flip[L] = k ^ 1
        buf = ring[k]
        if buf.inflight is not None:
            # the donated step that read this buffer two waves ago: its
            # output being ready proves the input buffer is consumed, so
            # rewriting rows below cannot race the device (and is safe
            # even if the host->device transfer aliased host memory)
            with TraceAnnotation("serve.stage_wait"):
                jax.block_until_ready(buf.inflight)
            self._count["stage_waits"] += 1
            buf.inflight = None
        if buf.dirty:
            rows = buf.dirty
            buf.batch[rows] = 0
            buf.valid[rows] = 0
            buf.dirty = []
        return buf

    def _dispatch(self) -> None:
        """Run the queued requests' waves: stage each wave into a
        double-buffered host batch and launch the donated step, WITHOUT
        reading decisions back. Wave composition is identical to the
        pre-async serial loop: one segment per session per wave, sessions
        coalesced, bucket = pow2 pad of the wave's longest segment."""
        if not self._queue:
            return
        with TraceAnnotation("serve.dispatch") as span:
            reqs, self._queue = self._queue, []
            self._queue_since = None
            pending = [list(r.segs) for r in reqs]
            wave_no = 0
            while any(pending):
                wave_no += 1
                self._launch_wave(reqs, pending, wave_no)
            span.set_metadata(waves=wave_no)
        self._dispatched.extend(reqs)

    def _launch_wave(self, reqs: list, pending: list, wave_no: int) -> None:
        """Stage, upload and launch the next wave of ``reqs``: one pending
        segment per session, ``wave_no`` counting the waves of this
        dispatch."""
        wave, seen, finals = [], set(), []
        for i, r in enumerate(reqs):
            if pending[i] and r.sid not in seen:
                wave.append((r, pending[i].pop(0)))
                seen.add(r.sid)
                if not pending[i]:
                    finals.append(r)
        L = bucket_length(max(seg.shape[0] for _, seg in wave),
                          self.min_chunk, self.max_chunk)
        step_no = self.steps_run           # the wave's id in every span
        with TraceAnnotation("serve.stage", wave=step_no, bucket=L):
            buf = self._stage_buffer(L)
            for r, seg in wave:
                slot = self._sessions[r.sid].slot
                buf.batch[slot, :seg.shape[0]] = seg
                buf.valid[slot] = seg.shape[0]
                buf.dirty.append(slot)
        nbytes = buf.batch.nbytes + buf.valid.nbytes
        with TraceAnnotation("serve.h2d", wave=step_no, bytes=nbytes):
            chunk_dev = jnp.asarray(buf.batch)
            valid_dev = jnp.asarray(buf.valid)
            if self._chunk_sharding is not None:
                chunk_dev = jax.device_put(chunk_dev, self._chunk_sharding)
                valid_dev = jax.device_put(valid_dev, self._valid_sharding)
        self._count["h2d_bytes"] += nbytes
        self._count["valid_samples"] += sum(seg.shape[0] for _, seg in wave)
        self._count["padded_samples"] += buf.batch.size
        # the step donates self._state: if the call raises, the old
        # buffers are already consumed and there is no state to roll
        # back to — mid-multi-wave the earlier waves are absorbed and
        # the rest never ran, so no resident register set is
        # trustworthy. Poison the server (feed/open fail loudly from
        # here on, naming this wave) rather than limping on with a
        # half-stepped or invalidated state.
        try:
            with TraceAnnotation("serve.launch", wave=step_no), \
                    self._compiling("launch"):
                self._state, p = self._step(self.pipeline, self._state,
                                            chunk_dev, valid_dev)
        except Exception as e:
            self._poisoned = (
                f"step raised {type(e).__name__} on wave {wave_no} of "
                f"a feed() call (bucket {L}, sessions "
                f"{sorted(r.sid for r, _ in wave)})")
            raise RuntimeError(
                f"feed() failed: {self._poisoned}; the donated session "
                "state was consumed by the failed call — the server "
                "is now poisoned") from e
        self.steps_run += 1
        self.bucket_counts[L] = self.bucket_counts.get(L, 0) + 1
        # NO host readback here: the decision array rides along
        # asynchronously and gates this buffer's reuse; requests
        # finishing on this wave are read back (vectorized) at the
        # next drain point. Slots are captured now — resolution may
        # happen after this session moved (it cannot close first:
        # close() flushes).
        buf.inflight = p
        if finals:
            self._inflight.append(
                (step_no, p,
                 [(r, self._sessions[r.sid].slot) for r in finals]))

    def _resolve(self) -> list:
        """Materialize every dispatched request's decision (ONE blocking
        readback per final-bearing wave, argmax vectorized over its
        finishing slots) and resolve tickets in submit order. Bit-for-bit
        the serial path's readback: same per-slot argmax on the same
        decision rows, same samples_seen bookkeeping order."""
        if not self._dispatched:
            return []
        with TraceAnnotation("serve.resolve"):
            for wave, p_dev, finals in self._inflight:
                with TraceAnnotation("serve.readback", wave=wave):
                    p_host = np.asarray(p_dev)  # blocks if not yet ready
                self._count["readbacks"] += 1
                slots = np.asarray([s for _, s in finals])
                rows = p_host[slots]
                labels = np.argmax(rows, axis=1)
                for (r, _), label, row in zip(finals, labels, rows):
                    r.label = int(label)
                    r.conf = float(row[label])
            self._inflight.clear()
            now = self._clock()
            results = []
            tickets = []
            for r in self._dispatched:
                sess = self._sessions[r.sid]
                # samples_seen advances by the WHOLE request, recorded once
                # on its final segment's decision
                total = sess.samples_seen + r.total
                d = Decision(samples_seen=total, label=r.label,
                             confidence=r.conf)
                sess.record(d, now)
                fr = FeedResult(session_id=r.sid, label=r.label,
                                confidence=r.conf, samples_seen=total)
                results.append(fr)
                if r.ticket.results is None:
                    r.ticket.results = [None] * r.ticket.n_requests
                    tickets.append(r.ticket)
                r.ticket.results[r.pos] = fr
            self._dispatched.clear()
        # a ticket is dispatched atomically (dispatch flushes the whole
        # queue), so every ticket touched here resolved completely
        assert all(None not in t.results for t in tickets)
        return results
