"""Async feed pipeline + routing tier contracts (PR 9).

What this file pins down:

* **submit/poll/drain semantics**: tickets resolve only at drain points,
  in request order, with ``feed()`` itself being submit+drain (one code
  path, parity by construction).
* **Coalescing bitwise parity**: many small ``submit()`` batches resolved
  by ONE ``drain()`` produce decisions and registers bit-for-bit equal to
  a single synchronous ``feed()`` of the concatenated requests — for BOTH
  numerics modes and BOTH stream impls. Wave composition differs between
  the paths (that is the whole point of coalescing); equality holds
  because the slot-batched step is row-parallel and zero-padding is
  inert.
* **Churn property**: random open/feed/evict/reopen lifecycles driven
  through the async path track a synchronous single-caller server
  register-exactly.
* **Watermark/deadline dispatch** and poisoned-state visibility through
  ``stats()``.
* **StreamRouter**: sharded serving is bitwise the single-server story,
  request order survives shard merging, backpressure errors name the
  shard, stats aggregate.

Randomization uses the hypothesis-or-fallback sampler in ``conftest.py``.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st

from repro.core import kernel_machine as km
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core.pipeline import InFilterPipeline
from repro.serving import StreamRouter, StreamServer, make_batched_step

pytestmark = pytest.mark.pallas

_BASE = dict(fs=8000.0, num_octaves=3, filters_per_octave=2, bp_taps=8,
             lp_taps=4, mode="mp", gamma_f=4.0)

_PIPES: dict = {}
_STEPS: dict = {}


def _pipe(numerics="float", stream_impl="xla"):
    key = (numerics, stream_impl)
    if key not in _PIPES:
        kw = dict(_BASE, stream_impl=stream_impl)
        if numerics == "fixed":
            kw.update(numerics="fixed", fixed_amax=3.0)
        cfg = FilterBankConfig(**kw)
        fb = FilterBank(cfg)
        P = cfg.num_filters
        clf = km.init_params(jax.random.PRNGKey(0), P, 4)
        mu = jax.random.normal(jax.random.PRNGKey(1), (P,)) * 0.1 + 1.0
        sigma = jnp.abs(jax.random.normal(jax.random.PRNGKey(2),
                                          (P,))) + 0.5
        _PIPES[key] = InFilterPipeline(cfg, fb.bp_by_octave, fb.lp_filters,
                                       mu, sigma, clf)
        # ONE compiled step per (numerics, impl) for the whole module —
        # fixed numerics jits a fresh closure per make_batched_step, so
        # sharing it is what keeps this file inside the compile budget
        _STEPS[key] = make_batched_step(_PIPES[key])
    return _PIPES[key]


def _server(numerics="float", stream_impl="xla", **kw):
    p = _pipe(numerics, stream_impl)
    kw.setdefault("max_chunk", 64)
    kw.setdefault("min_chunk", 16)
    kw.setdefault("capacity", 4)
    return StreamServer(p, step_fn=_STEPS[(numerics, stream_impl)], **kw)


_LENS = [5, 16, 33, 64, 100]    # buckets 16/32/64 (+ splits past 64)


def _results_key(results):
    return [(r.session_id, r.label, r.confidence, r.samples_seen)
            for r in results]


def _assert_state_bitwise(sa, sb, msg):
    for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# submit / poll / drain semantics
# ---------------------------------------------------------------------------


def test_submit_poll_drain_semantics():
    srv = _server()
    srv.open("a")
    srv.open("b")
    rng = np.random.default_rng(0)
    t1 = srv.submit([("a", rng.standard_normal(33).astype(np.float32))])
    t2 = srv.feed_async([("b", rng.standard_normal(16).astype(np.float32)),
                         ("a", rng.standard_normal(5).astype(np.float32))])
    assert not t1.done and not t2.done
    assert srv.poll(t1) is None                 # nothing dispatched yet
    assert srv.stats()["queued_requests"] == 3
    srv.drain()
    assert t1.done and t2.done
    assert [r.session_id for r in t2.results] == ["b", "a"]
    assert t2.results[1].samples_seen == 33 + 5  # a's submits in order
    assert srv.poll(t2) == t2.results           # poll after done: results
    assert srv.stats()["queued_requests"] == 0
    assert srv.stats()["unresolved_requests"] == 0
    # empty submit resolves immediately
    t0 = srv.submit([])
    assert t0.done and t0.results == []


def test_feed_is_submit_plus_drain_and_validates_atomically():
    srv = _server()
    srv.open("a")
    ok = np.zeros(16, np.float32)
    with pytest.raises(KeyError, match=r"session 'ghost' is not open"):
        srv.submit([("a", ok), ("ghost", ok)])
    with pytest.raises(ValueError, match="1-D"):
        srv.submit([("a", np.zeros((2, 16), np.float32))])
    with pytest.raises(ValueError, match="empty chunk"):
        srv.submit([("a", np.zeros(0, np.float32))])
    # failed validation enqueued NOTHING
    assert srv.stats()["queued_requests"] == 0
    res = srv.feed([("a", ok)])
    assert _results_key(res) == _results_key(srv.feed([("a", ok)])[:1]) \
        or res[0].samples_seen == 16


def test_watermark_dispatches_on_submit():
    srv = _server(coalesce_watermark=2)
    srv.open("a")
    srv.open("b")
    x = np.ones(16, np.float32)
    srv.submit([("a", x)])
    assert srv.stats()["queued_requests"] == 1      # below watermark
    assert srv.stats()["steps_run"] == 0
    t = srv.submit([("b", x)])
    assert srv.stats()["queued_requests"] == 0      # watermark hit
    assert srv.stats()["steps_run"] >= 1            # wave launched
    assert not t.done                               # readback deferred
    srv.drain()
    assert t.done


def test_deadline_dispatches_on_poll():
    srv = _server(coalesce_deadline=0.0)            # expires immediately
    srv.open("a")
    t = srv.submit([("a", np.ones(16, np.float32))])
    # deadline is cooperative: the next poll() dispatches, then resolves
    # once the device is done — bounded spin, no background thread
    for _ in range(1000):
        if srv.poll(t) is not None:
            break
    else:
        srv.drain()
    assert t.done
    assert t.results[0].samples_seen == 16


def test_lifecycle_calls_flush_the_queue(tmp_path):
    srv = _server(checkpoint_dir=str(tmp_path))
    srv.open("a")
    rng = np.random.default_rng(1)
    x = rng.standard_normal(40).astype(np.float32)
    t = srv.submit([("a", x)])
    srv.close("a", checkpoint=True)     # must absorb the queued feed
    assert t.done
    assert t.results[0].samples_seen == 40
    srv.open("a")                       # and the parked registers saw it
    assert srv.session("a").samples_seen == 40


# ---------------------------------------------------------------------------
# coalescing bitwise parity: async(submits)+drain == sync feed(concat)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("numerics,impl", [
    ("float", "xla"), ("float", "pallas"),
    ("fixed", "xla"), ("fixed", "pallas"),
])
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_async_coalescing_bitwise_matches_sync_feed(numerics, impl, seed):
    rng = np.random.default_rng(seed)
    ids = ["a", "b", "c"]
    reqs = []
    for _ in range(int(rng.integers(3, 9))):
        sid = ids[int(rng.integers(len(ids)))]
        ln = int(rng.choice(_LENS))
        reqs.append((sid, rng.standard_normal(ln).astype(np.float32)))

    srv_sync = _server(numerics, impl)
    srv_async = _server(numerics, impl)
    for srv in (srv_sync, srv_async):
        for sid in ids:
            srv.open(sid)
    res_sync = srv_sync.feed(reqs)

    # random split into k submit batches, ONE drain — different wave
    # composition than the sync path, same bits demanded
    tickets, i = [], 0
    while i < len(reqs):
        k = int(rng.integers(1, len(reqs) - i + 1))
        tickets.append(srv_async.submit(reqs[i:i + k]))
        i += k
    srv_async.drain()
    res_async = [r for t in tickets for r in t.results]

    assert _results_key(res_sync) == _results_key(res_async), \
        f"seed={seed} {numerics}/{impl}"
    _assert_state_bitwise(srv_sync.state, srv_async.state,
                          f"seed={seed} {numerics}/{impl}: registers")


# ---------------------------------------------------------------------------
# churn property: async path vs sync single-caller, register-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("numerics,impl", [
    ("float", "xla"), ("float", "pallas"),
    ("fixed", "xla"), ("fixed", "pallas"),
])
@settings(max_examples=2, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_async_churn_register_exact_vs_sync(numerics, impl, seed):
    # a fresh checkpoint store per example: a function-scoped tmp_path
    # would be shared by every example hypothesis draws
    with tempfile.TemporaryDirectory() as ckpt:
        _churn_register_exact_vs_sync(numerics, impl, seed, ckpt)


def _churn_register_exact_vs_sync(numerics, impl, seed, ckpt):
    rng = np.random.default_rng(seed)
    ids = [f"s{i}" for i in range(4)]
    srv_sync = _server(numerics, impl, capacity=3,
                       checkpoint_dir=os.path.join(ckpt, "sync"))
    srv_async = _server(numerics, impl, capacity=3,
                        checkpoint_dir=os.path.join(ckpt, "async"))
    open_set: set = set()
    tickets, expected = [], []

    for _ in range(25):
        op = rng.choice(["open", "feed", "evict", "close"],
                        p=[0.3, 0.45, 0.15, 0.1])
        sid = ids[int(rng.integers(len(ids)))]
        if op == "open" and sid not in open_set and len(open_set) < 3:
            srv_sync.open(sid)
            srv_async.open(sid)
            open_set.add(sid)
        elif op == "feed" and open_set:
            pool = sorted(open_set)
            batch = [(pool[int(rng.integers(len(pool)))],
                      rng.standard_normal(
                          int(rng.choice(_LENS))).astype(np.float32))
                     for _ in range(int(rng.integers(1, 4)))]
            expected.append(srv_sync.feed(batch))       # sync: immediate
            tickets.append(srv_async.submit(batch))     # async: queued
            if rng.random() < 0.4:
                srv_async.drain()
        elif op == "evict" and sid in open_set:
            srv_sync.evict(sid)
            srv_async.evict(sid)    # flushes srv_async's queue first
            open_set.discard(sid)
        elif op == "close" and sid in open_set:
            srv_sync.close(sid)
            srv_async.close(sid)
            open_set.discard(sid)
    srv_async.drain()

    for exp, t in zip(expected, tickets):
        assert t.done
        assert _results_key(exp) == _results_key(t.results), f"seed={seed}"
    _assert_state_bitwise(srv_sync.state, srv_async.state,
                          f"seed={seed} {numerics}/{impl}: churn registers")


# ---------------------------------------------------------------------------
# stats: async depth + poisoned visibility
# ---------------------------------------------------------------------------


def test_stats_surface_async_depth_and_bucket_totals():
    srv = _server()
    srv.open("a")
    srv.feed([("a", np.zeros(16, np.float32))])
    srv.feed([("a", np.zeros(33, np.float32))])
    s = srv.stats()
    assert s["poisoned"] is None
    assert s["bucket_steps_total"] == sum(s["buckets"].values()) >= 2
    assert abs(sum(s["bucket_hit_rate"].values()) - 1.0) < 1e-6
    assert s["queued_requests"] == 0
    assert s["inflight_waves"] == 0


def test_stats_surface_poisoned_string():
    srv = _server()
    srv.open("a")

    def bad_step(p, state, chunk, valid):
        raise RuntimeError("boom")

    srv._step = bad_step
    with pytest.raises(RuntimeError):
        srv.feed([("a", np.zeros(16, np.float32))])
    s = srv.stats()     # stats() must NOT raise on a poisoned server
    assert isinstance(s["poisoned"], str) and "wave 1" in s["poisoned"]


# ---------------------------------------------------------------------------
# routing tier
# ---------------------------------------------------------------------------


def test_router_bitwise_matches_single_server(tmp_path):
    pipe = _pipe()
    rng = np.random.default_rng(7)
    ids = [f"mic-{i:02d}" for i in range(8)]
    reqs = [(sid, rng.standard_normal(
        int(rng.choice(_LENS))).astype(np.float32)) for sid in ids]
    router = StreamRouter(pipe, num_shards=3, capacity=8,
                          checkpoint_dir=str(tmp_path),
                          step_fn=_STEPS[("float", "xla")],
                          max_chunk=64, min_chunk=16)
    single = _server(capacity=8)
    for sid in ids:
        router.open(sid)
        single.open(sid)
    res_r = router.feed(reqs)
    res_s = single.feed(reqs)
    assert _results_key(res_r) == _results_key(res_s)
    # shard mapping is stable and total residency is the sum
    assert all(router.shard_of(sid) == router.shard_of(sid) for sid in ids)
    st_ = router.stats()
    assert st_["resident"] == 8
    assert st_["poisoned"] is None
    assert len(st_["shards"]) == 3


def test_router_async_request_order_across_shards(tmp_path):
    router = StreamRouter(_pipe(), num_shards=2, capacity=8,
                          checkpoint_dir=str(tmp_path),
                          step_fn=_STEPS[("float", "xla")],
                          max_chunk=64, min_chunk=16)
    rng = np.random.default_rng(3)
    ids = [f"m{i}" for i in range(6)]
    for sid in ids:
        router.open(sid)
    # interleave shards in the request list; results must come back in
    # the ORIGINAL order, not shard-major
    order = [ids[i] for i in rng.permutation(len(ids))]
    reqs = [(sid, rng.standard_normal(16).astype(np.float32))
            for sid in order]
    t = router.submit(reqs)
    assert router.poll(t) is None
    router.drain()
    assert [r.session_id for r in t.results] == order
    t_empty = router.submit([])
    assert t_empty.done and t_empty.results == []


def test_router_churn_reopen_finds_shard_checkpoint(tmp_path):
    router = StreamRouter(_pipe(), num_shards=3, capacity=4,
                          checkpoint_dir=str(tmp_path),
                          step_fn=_STEPS[("float", "xla")],
                          max_chunk=64, min_chunk=16)
    rng = np.random.default_rng(5)
    router.open("edge-7")
    x = rng.standard_normal(100).astype(np.float32)
    r1 = router.feed([("edge-7", x[:64])])[0]
    router.evict("edge-7")
    assert not router.is_open("edge-7")
    router.open("edge-7")               # restored from its shard's store
    assert router.session("edge-7").samples_seen == 64
    r2 = router.feed([("edge-7", x[64:])])[0]
    # reference: uninterrupted single server
    srv = _server(capacity=2)
    srv.open("edge-7")
    q1 = srv.feed([("edge-7", x[:64])])[0]
    q2 = srv.feed([("edge-7", x[64:])])[0]
    assert _results_key([r1, r2]) == _results_key([q1, q2])


def test_router_backpressure_names_shard(tmp_path):
    router = StreamRouter(_pipe(), num_shards=2, capacity=1,
                          step_fn=_STEPS[("float", "xla")],
                          max_chunk=64, min_chunk=16)
    # find two ids on the same shard; no checkpoint_dir -> second open
    # must raise naming that shard
    by_shard: dict = {}
    for i in range(32):
        by_shard.setdefault(router.shard_of(f"x{i}"), []).append(f"x{i}")
    k, pair = next((k, v) for k, v in by_shard.items() if len(v) >= 2)
    router.open(pair[0])
    with pytest.raises(RuntimeError, match=rf"shard {k}: .*capacity"):
        router.open(pair[1])


def test_router_rejects_bad_config():
    with pytest.raises(ValueError, match="num_shards"):
        StreamRouter(_pipe(), num_shards=0)
