"""Routing tier: stream id -> server shard -> slot.

One admission API in front of N ``StreamServer`` shards. The shard for a
stream is a STABLE hash of its id (crc32, not Python's salted ``hash``),
so a session always lands on the same shard across processes and restarts
— which is what lets an evicted session find its parked checkpoint again:
each shard parks into its own ``checkpoint_dir`` subdirectory
(``shard-00``, ``shard-01``, ...).

All shards serve the SAME pipeline through one shared compiled step
(:func:`repro.serving.server.make_batched_step`), so N shards cost one
compile per chunk bucket, not N. Capacity scales linearly with shard
count while decisions stay bit-for-bit those of a single server holding
the same sessions: the slot-batched step is row-parallel, so a stream's
registers never depend on its co-tenants, its slot, or the shard's
capacity.

Backpressure is per shard: admission pressure on a full shard evicts that
shard's least-recently-fed idle session into its checkpoint store (or
raises, if there is nowhere to park — exactly the single-server
contract), and ``stats()`` surfaces per-shard residency/queue depth so a
hot shard is visible before it starts refusing streams.
"""

from __future__ import annotations

import dataclasses
import os
import zlib
from typing import Iterable, List, Optional, Union

from repro.core.pipeline import InFilterPipeline
from repro.serving.server import (COMPILE_SITES, COUNTERS, StreamServer,
                                  make_batched_step)
from repro.serving.session import FeedRequest, FeedResult, Session

__all__ = ["StreamRouter", "RouterTicket", "shard_of"]


def shard_of(session_id: str, num_shards: int) -> int:
    """Deterministic stream-id -> shard mapping (stable across runs)."""
    return zlib.crc32(session_id.encode("utf-8")) % num_shards


@dataclasses.dataclass
class RouterTicket:
    """Handle for one router ``submit()``: per-shard sub-tickets plus the
    request positions each covers, resolved back into request order."""
    n_requests: int
    parts: list                       # [(shard_idx, FeedTicket, [pos, ...])]
    results: Optional[List[FeedResult]] = None

    @property
    def done(self) -> bool:
        return self.results is not None

    def _try_assemble(self) -> None:
        if self.results is not None:
            return
        if not all(t.done for _, t, _ in self.parts):
            return
        out: list = [None] * self.n_requests
        for _, ticket, positions in self.parts:
            for res, pos in zip(ticket.results, positions):
                out[pos] = res
        self.results = out


class StreamRouter:
    """N ``StreamServer`` shards behind one admission/feed API.

    Parameters mirror ``StreamServer`` (they are applied per shard);
    ``capacity`` is PER SHARD, so total residency is
    ``num_shards * capacity``. ``checkpoint_dir`` (if given) fans out into
    one subdirectory per shard so eviction under churn works exactly as on
    a single server — per shard.
    """

    def __init__(self, pipeline: InFilterPipeline, num_shards: int = 2,
                 capacity: int = 64, *,
                 checkpoint_dir: Optional[str] = None, **server_kw):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        self.num_shards = num_shards
        self.pipeline = pipeline
        step = server_kw.pop("step_fn", None) or make_batched_step(
            pipeline, server_kw.get("mesh"))
        self._shards = []
        for k in range(num_shards):
            ck = None
            if checkpoint_dir is not None:
                ck = os.path.join(checkpoint_dir, f"shard-{k:02d}")
                os.makedirs(ck, exist_ok=True)
            self._shards.append(
                StreamServer(pipeline, capacity, checkpoint_dir=ck,
                             step_fn=step, **server_kw))
        self._tickets: List[RouterTicket] = []   # outstanding (not done)

    # -- admission / lifecycle ----------------------------------------------

    def shard_of(self, session_id: str) -> int:
        return shard_of(session_id, self.num_shards)

    def shard(self, k: int) -> StreamServer:
        return self._shards[k]

    @property
    def shards(self) -> list:
        return list(self._shards)

    def open(self, session_id: str) -> Session:
        k = self.shard_of(session_id)
        try:
            return self._shards[k].open(session_id)
        except RuntimeError as e:
            # per-shard backpressure, named: a full shard is THIS shard
            # being full — other shards may have room, but the id is pinned
            # to its hash (its checkpoints live here)
            raise RuntimeError(f"shard {k}: {e}") from e

    def close(self, session_id: str, *, checkpoint: bool = False) -> Session:
        return self._shards[self.shard_of(session_id)].close(
            session_id, checkpoint=checkpoint)

    def evict(self, session_id: str) -> Session:
        return self._shards[self.shard_of(session_id)].evict(session_id)

    def session(self, session_id: str) -> Session:
        return self._shards[self.shard_of(session_id)].session(session_id)

    def sessions(self) -> list:
        out = []
        for srv in self._shards:
            out.extend(srv.sessions())
        return out

    def is_open(self, session_id: str) -> bool:
        return session_id in self._shards[self.shard_of(session_id)]

    def __contains__(self, session_id: str) -> bool:
        return self.is_open(session_id)

    def stats(self) -> dict:
        per = [s.stats() for s in self._shards]
        return {
            "num_shards": self.num_shards,
            "capacity": sum(p["capacity"] for p in per),
            "resident": sum(p["resident"] for p in per),
            "steps_run": sum(p["steps_run"] for p in per),
            "queued_requests": sum(p["queued_requests"] for p in per),
            **{k: sum(p[k] for p in per) for k in COUNTERS},
            **{k: {w: sum(p[k][w] for p in per) for w in COMPILE_SITES}
               for k in ("compiles", "cache_loads")},
            "poisoned": {k: p["poisoned"] for k, p in enumerate(per)
                         if p["poisoned"] is not None} or None,
            "shards": per,
        }

    # -- feeding -------------------------------------------------------------

    def _split(self, requests) -> list:
        """Group requests by shard, preserving per-shard submit order and
        remembering each request's global position. Validates atomically
        ACROSS shards (unknown session / bad chunk raises before anything
        is enqueued anywhere)."""
        import numpy as np
        by_shard: dict[int, list] = {}
        n = 0
        for pos, r in enumerate(requests):
            if isinstance(r, FeedRequest):
                sid, chunk = r.session_id, r.chunk
            else:
                sid, chunk = r
            k = self.shard_of(sid)
            srv = self._shards[k]
            srv._check_poisoned()
            if sid not in srv:
                raise KeyError(f"session {sid!r} is not open")
            arr = np.asarray(chunk)
            if arr.ndim != 1:
                raise ValueError(
                    f"chunk for {sid!r} must be 1-D (samples,), got shape "
                    f"{arr.shape}")
            if arr.shape[0] == 0:
                raise ValueError(f"empty chunk for session {sid!r}")
            by_shard.setdefault(k, []).append((pos, sid, chunk))
            n = pos + 1
        return [(k, batch, n) for k, batch in sorted(by_shard.items())]

    def feed(self, requests: Iterable[Union[FeedRequest, tuple]]) -> list:
        """Synchronous feed across shards; results in request order."""
        ticket = self.submit(requests)
        self.drain()
        return ticket.results

    def feed_async(self, requests) -> RouterTicket:
        return self.submit(requests)

    def submit(self,
               requests: Iterable[Union[FeedRequest, tuple]]) -> RouterTicket:
        """Route each request to its shard's coalescing queue; returns a
        ``RouterTicket`` resolving to one ``FeedResult`` per request in
        request order at the next ``drain()``/ready ``poll()``."""
        groups = self._split(list(requests))
        n = max((g[2] for g in groups), default=0)
        parts = []
        for k, batch, _ in groups:
            sub = self._shards[k].submit([(sid, chunk)
                                          for _, sid, chunk in batch])
            parts.append((k, sub, [pos for pos, _, _ in batch]))
        ticket = RouterTicket(n_requests=n, parts=parts)
        if not parts:
            ticket.results = []
        else:
            self._tickets.append(ticket)
        return ticket

    def poll(self, ticket: RouterTicket) -> Optional[list]:
        if ticket.done:
            return ticket.results
        for k, sub, _ in ticket.parts:
            self._shards[k].poll(sub)
        ticket._try_assemble()
        if ticket.done:
            self._tickets = [t for t in self._tickets if not t.done]
            return ticket.results
        return None

    def drain(self) -> list:
        """Drain every shard, then assemble every outstanding router
        ticket. Returns all results resolved by this drain (shard-major
        order; use the tickets for request-order results)."""
        out = []
        for srv in self._shards:
            out.extend(srv.drain())
        for t in self._tickets:
            t._try_assemble()
        self._tickets = [t for t in self._tickets if not t.done]
        return out
