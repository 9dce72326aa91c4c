"""Session-oriented streaming API: unified apply(), slot-batched
SessionState (masked-slot inertness, per-slot ages, quantized running-amax
parity), StreamServer lifecycle (open/feed/evict/reopen), chunk bucketing,
and slot-axis sharding specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kernel_machine as km
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core.pipeline import (InFilterPipeline, SessionState,
                                 StreamingState, set_active)
from repro.serving import StreamServer, bucket_length


def _pipeline(num_octaves=3, filters_per_octave=3, num_classes=5,
              fs=8000.0, **cfg_over) -> InFilterPipeline:
    kw = dict(mode="mp", gamma_f=4.0)
    kw.update(cfg_over)
    cfg = FilterBankConfig(fs=fs, num_octaves=num_octaves,
                           filters_per_octave=filters_per_octave, **kw)
    fb = FilterBank(cfg)
    P = cfg.num_filters
    clf = km.init_params(jax.random.PRNGKey(0), P, num_classes)
    mu = jax.random.normal(jax.random.PRNGKey(1), (P,)) * 0.1 + 1.0
    sigma = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P,))) + 0.5
    return InFilterPipeline.from_filterbank(fb, clf, mu, sigma)


@pytest.fixture(scope="module")
def pipe():
    return _pipeline()


# ---------------------------------------------------------------------------
# unified apply()
# ---------------------------------------------------------------------------


def test_apply_stateless_matches_predict(pipe):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 512))
    np.testing.assert_array_equal(np.asarray(pipe.apply(x)),
                                  np.asarray(pipe.predict(x)))
    p, phi = pipe.apply(x, return_features=True)
    np.testing.assert_array_equal(np.asarray(phi),
                                  np.asarray(pipe.features(x)))


def test_apply_stateful_chunks_match_one_shot(pipe):
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 1500))
    p_one = pipe.predict(x)
    state = pipe.init_session(2)
    p = None
    for i in range(0, 1500, 77):                 # odd chunks + short tail
        p, state = pipe.apply(x[:, i:i + 77], state)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_one), atol=1e-4)
    assert int(state.count[0]) == 1500
    assert bool(state.active[0])


def test_apply_rejects_legacy_streaming_state(pipe):
    legacy = pipe.init_state(2)
    assert isinstance(legacy, StreamingState)
    with pytest.raises(TypeError, match="SessionState"):
        pipe.apply(jnp.zeros((2, 64)), legacy)


def test_apply_rejects_capacity_mismatch(pipe):
    state = pipe.init_session(4)
    with pytest.raises(ValueError, match="capacity"):
        pipe.apply(jnp.zeros((2, 64)), state)


def test_stream_dtype_mismatch_raises(pipe):
    chunks_ok = [np.zeros((1, 64), np.float32), np.zeros((1, 64), np.float32)]
    pipe.stream(chunks_ok)  # uniform dtype fine
    mixed = [np.zeros((1, 64), np.float32), np.zeros((1, 64), np.float16)]
    with pytest.raises(ValueError, match="dtype"):
        pipe.stream(mixed)
    with pytest.raises(ValueError, match="dtype"):
        pipe.stream([np.zeros((1, 64), np.float16)], dtype=jnp.float32)


# ---------------------------------------------------------------------------
# slot-batched sessions
# ---------------------------------------------------------------------------


def test_interleaved_slots_with_different_ages(pipe):
    """Two streams fed on disjoint schedules (per-slot valid counts and
    decimator phases) each match their dedicated one-shot decision."""
    xa = jax.random.normal(jax.random.PRNGKey(8), (1, 900))
    xb = jax.random.normal(jax.random.PRNGKey(9), (1, 900))
    pa_ref, pb_ref = pipe.predict(xa), pipe.predict(xb)
    state = pipe.init_session(2)
    ia = ib = 0
    p = None
    sched = [(0, 77), (1, 50), (0, 33), (1, 123), (0, 200), (1, 77),
             (0, 90), (1, 200), (0, 500), (1, 450)]
    for slot, ln in sched:
        chunk = np.zeros((2, ln), np.float32)
        v = np.zeros((2,), np.int32)
        if slot == 0:
            take = min(ln, 900 - ia)
            chunk[0, :take] = np.asarray(xa)[0, ia:ia + take]
            v[0] = take
            ia += take
        else:
            take = min(ln, 900 - ib)
            chunk[1, :take] = np.asarray(xb)[0, ib:ib + take]
            v[1] = take
            ib += take
        p, state = pipe.apply(jnp.asarray(chunk), state,
                              valid=jnp.asarray(v))
    assert (ia, ib) == (900, 900)
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(pa_ref[0]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(p[1]), np.asarray(pb_ref[0]),
                               atol=1e-4)


def test_masked_slots_are_inert_under_jit(pipe):
    """Inactive/zero-valid slots keep BIT-IDENTICAL registers even when
    their chunk rows hold garbage, and never perturb active slots."""
    app = jax.jit(InFilterPipeline.apply)
    state4 = pipe.init_session(4)
    state4 = set_active(state4, jnp.asarray([1, 3]), False)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 256)) * 100.0
    valid = jnp.asarray([256, 256, 100, 256], jnp.int32)  # 1,3 inert anyway
    p4, state4b = app(pipe, x, state4, valid=valid)
    # active rows equal a dedicated 2-slot session fed the same data
    rows = jnp.asarray([0, 2])
    p2, state2b = app(pipe, x[rows], pipe.init_session(2),
                      valid=valid[rows])
    np.testing.assert_array_equal(np.asarray(p4[rows]), np.asarray(p2))
    for a, b in zip(jax.tree.leaves(state4b._replace(active=None)),
                    jax.tree.leaves(state2b._replace(active=None))):
        np.testing.assert_array_equal(np.asarray(a)[np.asarray(rows)],
                                      np.asarray(b))
    # inactive rows bit-identical before/after
    idle = np.asarray([1, 3])
    for a, b in zip(jax.tree.leaves(state4), jax.tree.leaves(state4b)):
        np.testing.assert_array_equal(np.asarray(a)[idle],
                                      np.asarray(b)[idle])


def test_quantized_streaming_parity():
    """Unlocked by the running amax: with the stream's peak seen up front
    (first chunk, or a seeded calibration amax), quantized chunked apply()
    matches one-shot predict() — the old chunk-local scaling could not."""
    pipe_q = _pipeline(quant_bits=8)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 1200))
    x = x.at[:, 0].set(4.0)          # global amax lands in the first chunk
    p_one = pipe_q.predict(x)
    state = pipe_q.init_session(2)
    p = None
    for i in range(0, 1200, 160):
        p, state = pipe_q.apply(x[:, i:i + 160], state)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_one), atol=1e-4)
    # whole signal in ONE session chunk: bit-for-bit with one-shot
    p1, _, s1 = pipe_q.apply(x, pipe_q.init_session(2), return_features=True)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p_one))
    # seeded calibration amax equals the converged running amax
    amax = jnp.max(jnp.abs(x), axis=-1)
    st = pipe_q.init_session(2, amax=amax)
    p_c = None
    for i in range(0, 1200, 100):
        p_c, st = pipe_q.apply(x[:, i:i + 100], st)
    np.testing.assert_allclose(np.asarray(p_c), np.asarray(p_one), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(st.amax), np.asarray(amax))


# ---------------------------------------------------------------------------
# StreamServer lifecycle
# ---------------------------------------------------------------------------


def test_server_lifecycle_interleave_evict_reopen(pipe, tmp_path):
    """open -> feed interleaved -> auto-evict on admission pressure ->
    reopen restores from checkpoint -> decisions match dedicated streams."""
    rng = np.random.default_rng(0)
    xa = rng.standard_normal(900).astype(np.float32)
    xb = rng.standard_normal(900).astype(np.float32)
    xc = rng.standard_normal(400).astype(np.float32)
    ref_a = np.asarray(pipe.predict(jnp.asarray(xa)[None]))[0]
    ref_b = np.asarray(pipe.predict(jnp.asarray(xb)[None]))[0]
    t = [0.0]
    srv = StreamServer(pipe, capacity=2, max_chunk=512,
                       checkpoint_dir=str(tmp_path), clock=lambda: t[0])
    srv.open("a")
    srv.open("b")
    srv.feed([("a", xa[:77]), ("b", xb[:300])])
    t[0] += 1.0
    srv.feed([("b", xb[300:333]), ("a", xa[77:777])])  # a: 700 > 512 splits
    t[0] += 1.0
    srv.open("c")                       # full -> evicts LRU (a) to disk
    assert "a" not in {s.id for s in srv.sessions()}
    srv.feed([("c", xc), ("b", xb[333:900])])
    t[0] += 1.0
    srv.close("c")
    srv.open("a")                       # restores registers + history
    assert srv.session("a").samples_seen == 777
    assert len(srv.session("a").history) == 2
    res = srv.feed([("a", xa[777:900])])
    ra = res[0]
    assert ra.samples_seen == 900
    assert ra.label == int(ref_a.argmax())
    np.testing.assert_allclose(ra.confidence, ref_a[ra.label], atol=1e-4)
    db = srv.session("b").last_decision
    assert db.samples_seen == 900
    assert db.label == int(ref_b.argmax())
    np.testing.assert_allclose(db.confidence, ref_b[db.label], atol=1e-4)


def test_server_close_discards_reopen_starts_fresh(pipe, tmp_path):
    rng = np.random.default_rng(1)
    x = rng.standard_normal(500).astype(np.float32)
    srv = StreamServer(pipe, capacity=1, max_chunk=512,
                       checkpoint_dir=str(tmp_path))
    srv.open("s")
    r1 = srv.feed([("s", x)])[0]
    srv.close("s")                       # discard, not checkpoint
    srv.open("s")
    assert srv.session("s").samples_seen == 0
    r2 = srv.feed([("s", x)])[0]
    assert r2.samples_seen == 500
    np.testing.assert_allclose(r2.confidence, r1.confidence, atol=1e-6)


def test_server_capacity_without_checkpoint_raises(pipe):
    srv = StreamServer(pipe, capacity=1)
    srv.open("one")
    with pytest.raises(RuntimeError, match="capacity"):
        srv.open("two")
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        srv.evict("one")


def test_server_evict_after_protects_busy_sessions(pipe, tmp_path):
    t = [0.0]
    srv = StreamServer(pipe, capacity=1, evict_after=10.0,
                       checkpoint_dir=str(tmp_path), clock=lambda: t[0])
    srv.open("busy")
    srv.feed([("busy", np.zeros(32, np.float32))])
    t[0] = 5.0                           # idle 5 s < evict_after
    with pytest.raises(RuntimeError, match="capacity"):
        srv.open("newcomer")
    t[0] = 50.0                          # now idle long enough
    srv.open("newcomer")
    assert {s.id for s in srv.sessions()} == {"newcomer"}


def test_server_bucketing_bounds_retraces(pipe):
    """Arbitrary packet lengths compile only O(log L) step variants."""
    srv = StreamServer(pipe, capacity=1, min_chunk=16, max_chunk=256)
    srv.open("s")
    rng = np.random.default_rng(2)
    for n in [1, 5, 17, 31, 33, 47, 63, 65, 100, 129, 200, 255, 256]:
        srv.feed([("s", rng.standard_normal(n).astype(np.float32))])
    assert set(srv.bucket_counts) <= {16, 32, 64, 128, 256}
    # a 700-sample packet splits into max_chunk segments, no new bucket
    srv.feed([("s", rng.standard_normal(700).astype(np.float32))])
    assert set(srv.bucket_counts) <= {16, 32, 64, 128, 256}
    assert srv.session("s").samples_seen == sum(
        [1, 5, 17, 31, 33, 47, 63, 65, 100, 129, 200, 255, 256, 700])


def test_bucket_length():
    assert bucket_length(1, 16, 4096) == 16
    assert bucket_length(16, 16, 4096) == 16
    assert bucket_length(17, 16, 4096) == 32
    assert bucket_length(1000, 16, 4096) == 1024
    assert bucket_length(9000, 16, 4096) == 4096  # clamp; caller splits
    with pytest.raises(ValueError):
        bucket_length(0, 16, 4096)


def test_staging_buffer_reuse_no_cross_wave_leak(pipe):
    """The per-bucket staging buffers are REUSED across waves (slot-
    targeted clears, not fresh np.zeros): rows staged for one wave must
    never leak into a later wave that doesn't re-stage them."""
    rng = np.random.default_rng(4)
    xa = rng.standard_normal(64).astype(np.float32)
    xb = rng.standard_normal(64).astype(np.float32)
    xc = rng.standard_normal(40).astype(np.float32)
    srv = StreamServer(pipe, capacity=3, max_chunk=64)
    ref = StreamServer(pipe, capacity=3, max_chunk=64)
    for s in (srv, ref):
        for sid in ("a", "b", "c"):
            s.open(sid)
    srv.feed([("a", xa), ("b", xb)])     # stages rows 0,1 of bucket 64
    # same bucket, different slot: stale a/b rows must be cleared, and
    # c's decision must equal a server where a/b never fed at all
    r1 = srv.feed([("c", xc)])[0]
    r2 = ref.feed([("c", xc)])[0]
    assert (r1.label, r1.confidence, r1.samples_seen) == \
        (r2.label, r2.confidence, r2.samples_seen)
    # and the buffers really were reused: one staging array per flip, per
    # bucket (double-buffered ring), not one per wave
    assert len(srv._staging[64]) == 2


def test_server_feed_order_and_unknown_session(pipe):
    srv = StreamServer(pipe, capacity=2)
    srv.open("a")
    srv.open("b")
    with pytest.raises(KeyError):
        srv.feed([("ghost", np.zeros(16, np.float32))])
    res = srv.feed([("b", np.zeros(16, np.float32)),
                    ("a", np.zeros(16, np.float32))])
    assert [r.session_id for r in res] == ["b", "a"]


# ---------------------------------------------------------------------------
# slot-axis sharding
# ---------------------------------------------------------------------------


def test_session_specs_shard_slot_axis(pipe):
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as sh
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    state = pipe.init_session(4)
    specs = sh.session_specs(state, mesh)
    assert specs.acc == P(("data",), None)
    assert specs.amax == P(("data",))
    for d in specs.delays:
        assert d == P(("data",), None)


def test_server_with_mesh_matches_unsharded(pipe):
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)          # Auto axes, as the server expects
    rng = np.random.default_rng(3)
    chunks = [rng.standard_normal(64).astype(np.float32) for _ in range(3)]
    plain = StreamServer(pipe, capacity=2)
    sharded = StreamServer(pipe, capacity=2, mesh=mesh)
    for srv in (plain, sharded):
        srv.open("s")
    for ch in chunks:
        r0 = plain.feed([("s", ch)])[0]
        r1 = sharded.feed([("s", ch)])[0]
        assert r0.label == r1.label
        np.testing.assert_allclose(r0.confidence, r1.confidence, atol=1e-6)
