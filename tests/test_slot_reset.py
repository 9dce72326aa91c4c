"""The server's lifecycle program: ``open()`` and ``close()`` each launch
one jitted, donated ``pl.reset_slot`` instead of eager slot surgery.

After every open, close and evict the active mask and every active slot's
registers are bitwise what the eager ``clear_slots`` / ``put_slot`` /
``set_active`` sequence gives on a copy of the state taken before the call
(an inactive slot may differ only by being zero), every stream's last
decision is one-shot inference on its audio, one executable serves every
slot, and under a slot mesh every leaf keeps its sharding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixed
from repro.core import kernel_machine as km
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core.pipeline import (InFilterPipeline, clear_slots, put_slot,
                                 set_active, take_slot)
from repro.serving import StreamServer

S = 3
LENS = (16, 32, 64)
_PIPES: dict = {}


def _pipe(numerics: str) -> InFilterPipeline:
    if numerics not in _PIPES:
        kw = dict(fs=8000.0, num_octaves=3, filters_per_octave=2, bp_taps=8,
                  lp_taps=4, mode="mp", gamma_f=4.0, stream_impl="xla")
        if numerics == "fixed":
            kw.update(numerics="fixed", fixed_amax=3.0)
        cfg = FilterBankConfig(**kw)
        fb = FilterBank(cfg)
        P = cfg.num_filters
        mu = jax.random.normal(jax.random.PRNGKey(1), (P,)) * 0.1 + 1.0
        sigma = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P,))) + 0.5
        _PIPES[numerics] = InFilterPipeline(
            cfg, fb.bp_by_octave, fb.lp_filters, mu, sigma,
            km.init_params(jax.random.PRNGKey(0), P, 4))
    return _PIPES[numerics]


def _server(numerics: str, mesh: bool = False, **kw) -> StreamServer:
    if mesh:
        from repro.launch.mesh import make_host_mesh
        kw["mesh"] = make_host_mesh(1, 1)
    return StreamServer(_pipe(numerics), capacity=S, min_chunk=16,
                        max_chunk=64, **kw)


def _bits(a) -> bytes:
    return np.ascontiguousarray(a).tobytes()


def _assert_as_eager(got, want, what: str) -> None:
    """``got``'s mask and active rows bitwise ``want``'s; an inactive row
    is ``want``'s or zero."""
    got, want = jax.device_get(got), jax.device_get(want)
    assert _bits(got.active) == _bits(want.active), f"{what}: active mask"
    active = np.asarray(want.active)
    for k, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert g.dtype == w.dtype and g.shape == w.shape, f"{what}: leaf {k}"
        for s in range(S):
            same = _bits(g[s]) == _bits(w[s])
            assert same or (not active[s] and not np.asarray(g[s]).any()), \
                f"{what}: leaf {k}, slot {s}"


class _Lifecycle:
    """Drives a server and checks each lifecycle call against the eager
    helpers on a copy of the state taken before it."""

    def __init__(self, srv: StreamServer):
        self.srv = srv
        self.parked: dict = {}         # evicted id -> its parked row
        self.audio: dict = {}          # id -> audio since its stream began
        self.last: dict = {}           # id -> its last FeedResult

    def _check(self, before, want, what: str) -> None:
        _assert_as_eager(self.srv.state, want, what)
        shardings = jax.tree.map(lambda a: a.sharding, self.srv.state)
        assert shardings == before, f"{what}: a leaf changed its sharding"

    def _copy(self):
        return jax.tree.map(jnp.asarray, jax.device_get(self.srv.state))

    def open(self, sid: str) -> None:
        copy = self._copy()
        before = jax.tree.map(lambda a: a.sharding, self.srv.state)
        slot = self.srv.open(sid).slot
        want = clear_slots(copy, [slot])
        if sid in self.parked:
            want = put_slot(want, slot, self.parked.pop(sid))
        want = set_active(want, [slot], True)
        self._check(before, want, f"open {sid!r} into slot {slot}")

    def close(self, sid: str, evict: bool = False) -> None:
        copy = self._copy()
        before = jax.tree.map(lambda a: a.sharding, self.srv.state)
        slot = self.srv.session(sid).slot
        if evict:
            self.srv.evict(sid)
            self.parked[sid] = take_slot(copy, slot)
        else:
            self.srv.close(sid)
            self.audio.pop(sid)    # a reopen of this id starts fresh
        want = set_active(copy, [slot], False)
        self._check(before, want, f"close {sid!r} from slot {slot}")

    def feed(self, rng, sids) -> None:
        reqs = []
        for sid in sids:
            x = rng.standard_normal(int(rng.choice(LENS))).astype(np.float32)
            self.audio[sid] = np.concatenate([self.audio.get(sid, x[:0]), x])
            reqs.append((sid, x))
        for r in self.srv.feed(reqs):
            self.last[r.session_id] = (r, self.audio[r.session_id].copy())


def _scenario(srv: StreamServer) -> _Lifecycle:
    rng = np.random.default_rng(7)
    lc = _Lifecycle(srv)
    for sid in "abc":
        lc.open(sid)
    lc.feed(rng, "abc")
    lc.feed(rng, "ca")
    lc.close("b")                      # a rotation: d reuses b's slot
    lc.open("d")
    lc.feed(rng, "adc")
    lc.close("a", evict=True)          # parked through the checkpoint store
    lc.open("e")                       # takes a's slot
    lc.feed(rng, "ed")
    lc.close("c")
    lc.open("a")                       # restored into c's cleared slot
    lc.feed(rng, "aed")
    lc.close("d")
    return lc


def _assert_one_shot(numerics: str, lc: _Lifecycle) -> None:
    pipe = _pipe(numerics)
    for sid, (r, x) in sorted(lc.last.items()):
        assert r.samples_seen == x.shape[0], sid
        if numerics == "fixed":
            prog = pipe.fixed_program()
            p_q, _, _ = fixed.infer_q(
                prog, fixed.quantize_signal(prog, jnp.asarray(x)[None]))
            p = np.asarray(prog.out_spec.dequantize(p_q))[0]
            assert r.label == int(p.argmax()), sid
            assert r.confidence == float(p[r.label]), sid
        else:
            p = np.asarray(pipe.predict(jnp.asarray(x)[None]))[0]
            assert r.label == int(p.argmax()), sid
            np.testing.assert_allclose(r.confidence, p[r.label], atol=1e-4,
                                       err_msg=sid)


@pytest.mark.parametrize("numerics,mesh", [
    ("float", False), ("fixed", False), ("fixed", True)])
def test_lifecycle_program_matches_eager_surgery(numerics, mesh, tmp_path):
    srv = _server(numerics, mesh, checkpoint_dir=str(tmp_path))
    lc = _scenario(srv)
    assert sorted(lc.last) == list("abcde")
    _assert_one_shot(numerics, lc)
    # one program per open and per close, one row write per restore
    assert srv.stats()["slot_resets"] == 6 + 4 + 1


@pytest.fixture
def no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("numerics,mesh", [
    ("float", False), ("fixed", False), ("fixed", True)])
def test_lifecycle_program_compiles_once(numerics, mesh,
                                         no_persistent_cache):
    """The slot and the flag are traced: after the first open, opens and
    closes on other slots compile nothing."""
    jax.clear_caches()
    srv = _server(numerics, mesh)
    srv.open("s0")
    first = srv.stats()["compiles"]["lifecycle"]
    assert first > 0
    srv.open("s1")
    srv.open("s2")
    srv.close("s1")
    srv.close("s0")
    srv.open("s3")
    assert srv.stats()["compiles"]["lifecycle"] == first
