"""The server's instrumentation: its ``stats()`` counters count exactly the
work a known sequence of calls does, compiles are charged to the call that
made them, and its ``serve.*`` profiler spans nest as documented in
``docs/serving.md`` and carry the wave ids of the steps they belong to.

The scenario: four slots, buckets 16 to 64, packets of 10 to 84 samples
(one split across two waves), one stream rotation, a submit flushed by a
checkpointing close, and the reopen that restores it.
"""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import kernel_machine as km
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core.pipeline import InFilterPipeline
from repro.serving import StreamRouter, StreamServer, make_batched_step
from repro.serving.server import COMPILE_SITES, COUNTERS

OCTAVES = 3
S = 4
_PIPES: dict = {}


def _pipe(numerics: str) -> InFilterPipeline:
    if numerics not in _PIPES:
        kw = dict(fs=8000.0, num_octaves=OCTAVES, filters_per_octave=2,
                  bp_taps=8, lp_taps=4, mode="mp", gamma_f=4.0,
                  stream_impl="xla")
        if numerics == "fixed":
            kw.update(numerics="fixed", fixed_amax=3.0)
        cfg = FilterBankConfig(**kw)
        fb = FilterBank(cfg)
        P = cfg.num_filters
        _PIPES[numerics] = InFilterPipeline(
            cfg, fb.bp_by_octave, fb.lp_filters, jnp.ones((P,)),
            jnp.ones((P,)), km.init_params(jax.random.PRNGKey(0), P, 3))
    return _PIPES[numerics]


def _server(numerics: str, **kw) -> StreamServer:
    return StreamServer(_pipe(numerics), capacity=S, min_chunk=16,
                        max_chunk=64, **kw)


def _audio(n: int, seed: int = 0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,))


def _scenario(srv: StreamServer) -> None:
    for sid in "abcd":
        srv.open(sid)
    srv.submit([("a", _audio(10)), ("b", _audio(40, 1))])
    srv.submit([("c", _audio(84, 2))])     # 64 + 20: two waves
    srv.drain()                            # waves 0 (bucket 64), 1 (32)
    srv.close("a")                         # a rotation: e takes a's slot
    srv.open("e")
    srv.feed([("e", _audio(16, 3)), ("d", _audio(16, 4))])    # wave 2
    srv.feed([("e", _audio(16, 5))])                          # wave 3
    srv.submit([("d", _audio(16, 6))])
    srv.close("d", checkpoint=True)        # flushes wave 4, then parks d
    srv.open("d")                          # restores d


# per wave of the scenario: (bucket, valid samples)
WAVES = [(64, 10 + 40 + 64), (32, 20), (16, 32), (16, 16), (16, 16)]
# lifecycle programs launched outside the step: one slot reset per open
# (a, b, c, d, e and d's reopen) and per close (a, and d's checkpointing
# close), and one row write per restore (d's reopen)
OPENS, CLOSES, RESTORES = 6, 2, 1
EXPECTED = {
    "drains": 3,                           # drain() and two feed()s
    "readbacks": 5,                        # every wave carries a final
    "stage_waits": 1,                      # wave 4 reuses wave 2's buffer
    "h2d_bytes": sum(S * L * 4 + S * 4 for L, _ in WAVES),
    "valid_samples": sum(v for _, v in WAVES),
    "padded_samples": sum(S * L for L, _ in WAVES),
    "slot_resets": OPENS + CLOSES + RESTORES,
}


@pytest.mark.parametrize("numerics", ["float", "fixed"])
def test_counters_count_the_scenario_exactly(numerics, tmp_path):
    srv = _server(numerics, checkpoint_dir=str(tmp_path))
    _scenario(srv)
    stats = srv.stats()
    assert {k: stats[k] for k in COUNTERS} == EXPECTED
    assert stats["steps_run"] == len(WAVES)
    assert stats["buckets"] == {16: 3, 32: 1, 64: 1}
    for k in ("compiles", "cache_loads"):
        assert set(stats[k]) == set(COMPILE_SITES)


@pytest.fixture
def no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("numerics", ["float", "fixed"])
def test_compiles_charged_to_a_buckets_first_wave(numerics,
                                                  no_persistent_cache):
    jax.clear_caches()
    srv = _server(numerics, step_fn=make_batched_step(_pipe(numerics)))
    srv.open("a")

    def launch_compiles(n: int) -> int:
        before = srv.stats()["compiles"]["launch"]
        srv.feed([("a", _audio(n))])
        assert srv.stats()["cache_loads"]["launch"] == 0
        return srv.stats()["compiles"]["launch"] - before

    assert launch_compiles(16) > 0         # bucket 16's first wave
    assert launch_compiles(16) == 0        # a repeat
    assert launch_compiles(30) > 0         # bucket 32's first wave
    assert launch_compiles(20) == 0        # bucket 32 again


def test_router_sums_the_shards_counters(tmp_path):
    router = StreamRouter(_pipe("float"), num_shards=2, capacity=2 * S,
                          min_chunk=16, max_chunk=64)
    ids = [f"s{i}" for i in range(6)]     # four on shard 0, two on 1
    for sid in ids:
        router.open(sid)
    router.feed([(sid, _audio(10 + 9 * i, i)) for i, sid in enumerate(ids)])
    router.close(ids[0])
    stats = router.stats()
    for k in COUNTERS:
        assert stats[k] == sum(p[k] for p in stats["shards"])
    for k in ("compiles", "cache_loads"):
        assert stats[k] == {w: sum(p[k][w] for p in stats["shards"])
                            for w in COMPILE_SITES}
    assert stats["drains"] == 2 and stats["readbacks"] == 2


# the span each serve.* span opens inside (None: a top-level call)
PARENTS = {
    "serve.open": {None},
    "serve.close": {None},
    "serve.submit": {None},
    "serve.flush": {"serve.open", "serve.close"},
    "serve.dispatch": {None, "serve.flush"},
    "serve.stage": {"serve.dispatch"},
    "serve.stage_wait": {"serve.stage"},
    "serve.h2d": {"serve.dispatch"},
    "serve.launch": {"serve.dispatch"},
    "serve.resolve": {None, "serve.flush"},
    "serve.readback": {"serve.resolve"},
    "serve.slot_reset": {"serve.open", "serve.close"},
    "serve.restore": {"serve.open"},
    "serve.park": {"serve.close"},
}


def _serve_spans(log_dir: str) -> list:
    """``(name, start, end, metadata, parent name)`` of every ``serve.*``
    span in the trace under ``log_dir``; the parent is the innermost
    ``serve.*`` span around it on the same thread."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = sorted(((e.start_ns, -e.duration_ns, e.name,
                          dict(e.stats)) for e in line.events
                         if e.name.startswith("serve.")),
                        key=lambda t: t[:2])
            open_ = []                     # (end, name) of enclosing spans
            for s, neg_d, name, meta in ev:
                e = s - neg_d
                while open_ and open_[-1][0] <= s:
                    open_.pop()
                out.append((name, s, e, meta,
                            open_[-1][1] if open_ else None))
                open_.append((e, name))
    return out


def test_spans_nest_and_carry_wave_ids(tmp_path):
    srv = _server("float", checkpoint_dir=str(tmp_path / "ck"))
    srv.open("warm")                       # compile off the trace
    srv.feed([("warm", _audio(16))])
    srv.close("warm")
    srv = _server("float", checkpoint_dir=str(tmp_path / "ck2"),
                  step_fn=srv._step)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _scenario(srv)
    finally:
        jax.profiler.stop_trace()
    spans = _serve_spans(str(tmp_path / "trace"))
    names = [n for n, *_ in spans]
    assert set(names) == set(PARENTS)
    for name, _, _, _, parent in spans:
        assert parent in PARENTS[name], (name, parent)
    # one span per operation: per wave one stage, h2d, launch, readback
    for kind in ("serve.stage", "serve.h2d", "serve.launch",
                 "serve.readback"):
        waves = sorted(m["wave"] for n, _, _, m, _ in spans if n == kind)
        assert waves == list(range(len(WAVES))), kind
    stage = {m["wave"]: m["bucket"] for n, _, _, m, _ in spans
             if n == "serve.stage"}
    assert stage == {w: L for w, (L, _) in enumerate(WAVES)}
    h2d = {m["wave"]: m["bytes"] for n, _, _, m, _ in spans
           if n == "serve.h2d"}
    assert sum(h2d.values()) == EXPECTED["h2d_bytes"]
    assert names.count("serve.open") == 6 and names.count("serve.close") == 2
    assert names.count("serve.stage_wait") == EXPECTED["stage_waits"]
    assert sorted(m["slot"] for n, _, _, m, _ in spans
                  if n == "serve.open") == [0, 0, 1, 2, 3, 3]
    assert sum(m["requests"] for n, _, _, m, _ in spans
               if n == "serve.submit") == 2 + 1 + 2 + 1 + 1
    assert sum(m["waves"] for n, _, _, m, _ in spans
               if n == "serve.dispatch") == len(WAVES)
