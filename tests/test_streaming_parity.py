"""Property-based chunking-parity harness for the streaming session step.

The deployment contract this suite pins down (ISSUE 3 / ROADMAP "Pallas
streaming kernel"):

* **Streaming == one-shot**: feeding a signal through ``apply(x, state)`` in
  ANY chunk partition — including length-0 and length-1 chunks, per-slot
  valid counts, and interleaved slot lifecycles — yields the same decisions
  as one-shot ``apply(x)`` to f32 round-off (identical FIR windows and MP
  solves; only cross-chunk accumulator addition order differs).
* **Pallas == XLA, bit-for-bit**: the stateful ``fir_mp_stream`` kernel
  (``stream_impl="pallas"``) and the XLA session step agree EXACTLY in
  interpret mode — same solver math on the same window values, same blocked
  HWR reduction order — for every register in the ``SessionState``, not just
  the decisions.
* **Single chunk == one-shot, bit-for-bit**: with the whole signal in one
  call, both streaming impls reproduce the one-shot accumulate exactly
  (shared ``hwr_accumulate`` blocking).
* **Fixed-point streaming == one-shot, bit-for-bit, ANY chunking** (PR 5):
  with ``numerics="fixed"`` the int32 session step must land on EXACTLY
  the one-shot integer program's codes — registers and decisions gate with
  ``==`` from the first chunk (static ADC grid, associative integer adds;
  docs/numerics.md).
* **Int Pallas == int XLA == one-shot, bit-for-bit** (PR 6): with
  ``numerics="fixed"`` + ``stream_impl="pallas"`` the VMEM-resident
  integer kernel (``fir_mp_stream_q``) must track the int XLA session step
  register-for-register under random chunkings and slot lifecycles, and
  land on the one-shot program exactly — the same ``==`` gate, through the
  jitted step and the StreamServer.

Randomization comes through the hypothesis-or-fallback sampler in
``conftest.py``: each example draws one seed; numpy generates audio, chunk
partitions, and slot schedules from it, so the harness runs identically
with or without hypothesis installed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st

from repro.core import kernel_machine as km
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core.pipeline import InFilterPipeline, set_active

pytestmark = pytest.mark.pallas

# small bank, short taps: T1 = 7 keeps delay lines tight so length-1 chunks
# and phase flips get real coverage without hiding behind long histories
_BASE = dict(fs=8000.0, num_octaves=3, filters_per_octave=2, bp_taps=8,
             lp_taps=4, mode="mp", gamma_f=4.0)


def _make_pipelines(**cfg_over):
    """One trained-shape pipeline per stream_impl, sharing taps/weights."""
    kw = dict(_BASE)
    kw.update(cfg_over)
    cfg = FilterBankConfig(**kw)
    fb = FilterBank(cfg)
    P = cfg.num_filters
    clf = km.init_params(jax.random.PRNGKey(0), P, 4)
    mu = jax.random.normal(jax.random.PRNGKey(1), (P,)) * 0.1 + 1.0
    sigma = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P,))) + 0.5
    pipes = {}
    for impl in ("xla", "pallas"):
        pipes[impl] = InFilterPipeline(
            cfg._replace(stream_impl=impl), fb.bp_by_octave, fb.lp_filters,
            mu, sigma, clf)
    return pipes["xla"], pipes["pallas"]


_PIPES = {}

# one jitted apply for the whole suite: the pipeline rides along as a pytree
# (config is static aux data), so each (impl, config, chunk-shape) variant
# compiles once and is reused across property examples — the same retrace
# bounding the serving layer gets from pow2 chunk buckets
_APP = jax.jit(InFilterPipeline.apply)


def _pipes(**cfg_over):
    key = tuple(sorted(cfg_over.items()))
    if key not in _PIPES:
        _PIPES[key] = _make_pipelines(**cfg_over)
    return _PIPES[key]


# Chunk lengths are drawn from a fixed menu: every distinct (S, L) retraces
# the jitted kernel wrapper (exactly like serving's pow2 buckets bound
# retraces in production), so an unbounded draw would spend the whole suite
# compiling. The menu still covers the edge cases that matter: empty calls,
# single samples, odd lengths (decimator phase flips), and multi-block
# lengths (129 > two 64-blocks; 513 spills into a second 512-block upstream).
_LEN_MENU = [0, 1, 3, 8, 13, 32, 77, 129]


def _partition(rng, max_chunks=6):
    """Random chunk-length sequence from the menu; returns (lens, total).
    Always includes at least one 0- and one 1-length chunk."""
    k = int(rng.integers(1, max_chunks + 1))
    lens = [int(rng.choice(_LEN_MENU)) for _ in range(k)] + [0, 1]
    rng.shuffle(lens)
    if sum(lens) == 0:
        lens.append(int(rng.choice(_LEN_MENU[4:])))
    return lens, sum(lens)


def _assert_states_bitwise(sa, sb, msg):
    for name, a, b in zip(sa._fields, sa, sb):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb),
                err_msg=f"{msg}: SessionState.{name} diverged")


# ---------------------------------------------------------------------------
# the core property: random chunkings
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_random_chunking_stream_matches_one_shot_and_pallas_matches_xla(seed):
    rng = np.random.default_rng(seed)
    px, pk = _pipes()
    S = 2
    lens, n = _partition(rng)
    x = jnp.asarray(rng.standard_normal((S, n)).astype(np.float32))
    p_one = _APP(px, x)

    sx, sk = px.init_session(S), pk.init_session(S)
    p_x = p_k = None
    off = 0
    for ln in lens:
        ch = x[:, off:off + ln]
        off += ln
        p_x, sx = _APP(px, ch, sx)
        p_k, sk = _APP(pk, ch, sk)
        np.testing.assert_array_equal(
            np.asarray(p_x), np.asarray(p_k),
            err_msg=f"seed={seed}: xla/pallas decisions diverged at {off}")
    _assert_states_bitwise(sx, sk, f"seed={seed}")
    np.testing.assert_allclose(np.asarray(p_x), np.asarray(p_one),
                               atol=1e-4,
                               err_msg=f"seed={seed}: stream vs one-shot")
    assert int(sx.count[0]) == n


@pytest.mark.parametrize("solver", ["newton", "bisect"])
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_solver_choices_agree_bitwise(solver, seed):
    """Both fixed-iteration solvers route through both impls identically."""
    rng = np.random.default_rng(seed)
    px, pk = _pipes(solver=solver)
    lens, n = _partition(rng, max_chunks=3)
    x = jnp.asarray(rng.standard_normal((2, n)).astype(np.float32))
    sx, sk = px.init_session(2), pk.init_session(2)
    off = 0
    for ln in lens:
        ch = x[:, off:off + ln]
        off += ln
        p_x, sx = _APP(px, ch, sx)
        p_k, sk = _APP(pk, ch, sk)
        np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_k))
    _assert_states_bitwise(sx, sk, f"seed={seed} solver={solver}")


# ---------------------------------------------------------------------------
# slot lifecycles: open / feed / close in random orders
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_random_slot_lifecycles_parity(seed):
    """S slots on random open/feed/close schedules with per-slot valid
    counts: every slot's final decision matches its dedicated one-shot run,
    and the two impls track each other bit-for-bit throughout."""
    rng = np.random.default_rng(seed)
    px, pk = _pipes()
    S = 3
    total = [int(rng.integers(40, 200)) for _ in range(S)]
    audio = [rng.standard_normal(t).astype(np.float32) for t in total]
    fed = [0] * S
    opened = [False] * S
    closed = [False] * S

    sx, sk = px.init_session(S), pk.init_session(S)
    sx = set_active(sx, jnp.arange(S), False)
    sk = set_active(sk, jnp.arange(S), False)
    last_p = [None] * S

    for _ in range(25):
        slot = int(rng.integers(S))
        if not opened[slot]:
            opened[slot] = True
            sx = set_active(sx, jnp.asarray([slot]), True)
            sk = set_active(sk, jnp.asarray([slot]), True)
            continue
        if closed[slot]:
            continue
        take = min(int(rng.choice(_LEN_MENU)), total[slot] - fed[slot])
        # pad bucket: smallest menu length covering `take` (valid counts are
        # traced values; only the chunk SHAPE keys a retrace)
        L = min((l for l in _LEN_MENU if l >= max(take, 1)),
                default=_LEN_MENU[-1])
        chunk = np.zeros((S, L), np.float32)
        # non-fed rows carry garbage that the valid mask must neutralize
        chunk[:] = rng.standard_normal((S, L)) * 50.0
        chunk[slot, :take] = audio[slot][fed[slot]:fed[slot] + take]
        valid = np.zeros((S,), np.int32)
        valid[slot] = take
        fed[slot] += take
        p_x, sx = _APP(px, jnp.asarray(chunk), sx, valid=jnp.asarray(valid))
        p_k, sk = _APP(pk, jnp.asarray(chunk), sk, valid=jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_k),
                                      err_msg=f"seed={seed}")
        last_p[slot] = np.asarray(p_x[slot])
        if fed[slot] == total[slot]:
            closed[slot] = True
            sx = set_active(sx, jnp.asarray([slot]), False)
            sk = set_active(sk, jnp.asarray([slot]), False)

    _assert_states_bitwise(sx, sk, f"seed={seed}")
    for s in range(S):
        if not closed[s]:
            continue
        ref = np.asarray(_APP(px, jnp.asarray(audio[s])[None]))[0]
        np.testing.assert_allclose(last_p[s], ref, atol=1e-4,
                                   err_msg=f"seed={seed} slot={s}")


# ---------------------------------------------------------------------------
# bit-for-bit single-chunk and quantized deployment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 100, 513, 1200])
def test_single_chunk_is_bitwise_one_shot_both_impls(n):
    """Whole signal in ONE session call == one-shot predict, bit-for-bit,
    through either impl (the shared blocked HWR reduction order)."""
    px, pk = _pipes()
    x = jax.random.normal(jax.random.PRNGKey(n), (2, n))
    p_one = np.asarray(_APP(px, x))
    for pipe in (px, pk):
        p, state = _APP(pipe, x, pipe.init_session(2))
        np.testing.assert_array_equal(np.asarray(p), p_one)
        assert int(state.count[0]) == n


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_quantized_streaming_parity_pallas(seed):
    """Quantized deployment: running-amax semantics are identical across
    impls (bitwise), and with a seeded calibration amax the stream matches
    one-shot to f32 round-off."""
    rng = np.random.default_rng(seed)
    px, pk = _pipes(quant_bits=8)
    lens, n = _partition(rng, max_chunks=4)
    x = rng.standard_normal((2, n)).astype(np.float32)
    x[:, 0] = 3.5                    # a known global peak
    x = jnp.asarray(x)
    p_one = _APP(px, x)
    amax0 = jnp.max(jnp.abs(x), axis=-1)
    sx = px.init_session(2, amax=amax0)
    sk = pk.init_session(2, amax=amax0)
    off = 0
    p_x = p_k = None
    for ln in lens:
        ch = x[:, off:off + ln]
        off += ln
        p_x, sx = _APP(px, ch, sx)
        p_k, sk = _APP(pk, ch, sk)
        np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_k))
    _assert_states_bitwise(sx, sk, f"seed={seed}")
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_one), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(sk.amax), np.asarray(amax0))


# ---------------------------------------------------------------------------
# edges: inert slots, zero-length calls, jit, mac guard
# ---------------------------------------------------------------------------


def test_masked_slots_inert_under_jit_pallas():
    """Garbage rows behind active=False / valid=0 leave every register
    bit-identical through the Pallas kernel, under jit."""
    _, pk = _pipes()
    app = jax.jit(InFilterPipeline.apply)
    state = pk.init_session(4)
    state = set_active(state, jnp.asarray([1, 3]), False)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 300)) * 100.0
    valid = jnp.asarray([300, 123, 0, 7], jnp.int32)   # 1, 3 inert anyway
    p, state2 = app(pk, x, state, valid=valid)
    # 1, 3: inactive; 2: active but zero valid — all must be bit-identical
    idle = np.asarray([1, 2, 3])
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
        np.testing.assert_array_equal(np.asarray(a)[idle],
                                      np.asarray(b)[idle])


def test_zero_length_chunk_is_pure_readout():
    """A (S, 0) chunk moves no registers and reads out the current
    decision — identically for both impls."""
    px, pk = _pipes()
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 150))
    for pipe in (px, pk):
        state = pipe.init_session(2)
        p1, state = _APP(pipe, x, state)
        p0, state2 = _APP(pipe, jnp.zeros((2, 0)), state)
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jitted_session_step_matches_eager_pallas():
    _, pk = _pipes()
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 257))
    app = jax.jit(InFilterPipeline.apply)
    p_e, s_e = pk.apply(x, pk.init_session(2))
    p_j, s_j = app(pk, x, pk.init_session(2))
    np.testing.assert_array_equal(np.asarray(p_e), np.asarray(p_j))
    _assert_states_bitwise(s_e, s_j, "jit vs eager")


def test_mac_mode_rejects_pallas_stream_impl():
    px, _ = _pipes()
    cfg = px.config._replace(mode="mac", stream_impl="pallas")
    fb = FilterBank(cfg)
    pipe = InFilterPipeline(cfg, fb.bp_by_octave, fb.lp_filters,
                            px.mu, px.sigma, px.clf)
    with pytest.raises(ValueError, match="pallas"):
        pipe.apply(jnp.zeros((2, 64)), pipe.init_session(2))


# ---------------------------------------------------------------------------
# fixed-point (int32) session streaming: EXACT equality, not allclose —
# the ADC grid is static and integer addition is associative, so any chunk
# partition must reproduce the one-shot integer program bit-for-bit from
# the FIRST chunk (no peak-seen caveat, unlike quant_bits float streaming)
# ---------------------------------------------------------------------------


_FIXED_PIPES = {}


def _fixed_pipe(**cfg_over):
    """A numerics='fixed' pipeline + its closure-jitted session step (the
    program lowers host-side, so the pipeline must NOT ride along as a
    traced pytree the way _APP passes it)."""
    key = tuple(sorted(cfg_over.items()))
    if key not in _FIXED_PIPES:
        kw = dict(_BASE, numerics="fixed", fixed_amax=3.0)
        kw.update(cfg_over)
        cfg = FilterBankConfig(**kw)
        fb = FilterBank(cfg)
        P = cfg.num_filters
        clf = km.init_params(jax.random.PRNGKey(0), P, 4)
        mu = jax.random.normal(jax.random.PRNGKey(1), (P,)) * 0.1 + 1.0
        sigma = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (P,))) + 0.5
        pipe = InFilterPipeline(cfg, fb.bp_by_octave, fb.lp_filters,
                                mu, sigma, clf)
        app = jax.jit(lambda st, ch, v: pipe.apply(ch, st, valid=v))
        _FIXED_PIPES[key] = (pipe, app)
    return _FIXED_PIPES[key]


@pytest.mark.parametrize("mode", ["mp", "mac"])
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_fixed_random_chunking_is_bitwise_one_shot(mode, seed):
    """Random chunk partitions through the int32 session step reproduce the
    one-shot integer program EXACTLY: decisions, features, and the 32-bit
    accumulator registers all gate with ==, from the first chunk."""
    from repro.core import fixed

    rng = np.random.default_rng(seed)
    pipe, app = _fixed_pipe(mode=mode)
    prog = pipe.fixed_program()
    S = 2
    lens, n = _partition(rng)
    x = jnp.asarray(rng.standard_normal((S, n)).astype(np.float32))
    p_q, phi_q, s_q = fixed.infer_q(prog, fixed.quantize_signal(prog, x))
    p_one = prog.out_spec.dequantize(p_q)

    state = pipe.init_session(S)
    assert state.acc.dtype == jnp.int32
    assert all(d.dtype == jnp.int32 for d in state.delays)
    p_s = None
    off = 0
    for ln in lens:
        ch = x[:, off:off + ln]
        off += ln
        p_s, state = app(state, ch, jnp.full((S,), ln, jnp.int32))
    np.testing.assert_array_equal(np.asarray(state.acc), np.asarray(s_q),
                                  err_msg=f"seed={seed}: acc registers")
    np.testing.assert_array_equal(np.asarray(p_s), np.asarray(p_one),
                                  err_msg=f"seed={seed}: decisions")
    assert int(state.count[0]) == n


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_fixed_slot_lifecycles_bitwise(seed):
    """Random open/feed/close lifecycles with per-slot valid counts: every
    completed slot's decision equals its dedicated one-shot integer run
    EXACTLY, and garbage in non-fed rows never perturbs a register."""
    rng = np.random.default_rng(seed)
    pipe, app = _fixed_pipe()
    S = 3
    total = [int(rng.integers(40, 200)) for _ in range(S)]
    audio = [rng.standard_normal(t).astype(np.float32) for t in total]
    fed = [0] * S
    state = pipe.init_session(S)
    last_p = [None] * S
    for _ in range(20):
        slot = int(rng.integers(S))
        take = min(int(rng.choice(_LEN_MENU)), total[slot] - fed[slot])
        L = min((l for l in _LEN_MENU if l >= max(take, 1)),
                default=_LEN_MENU[-1])
        chunk = (rng.standard_normal((S, L)) * 50.0).astype(np.float32)
        chunk[slot, :take] = audio[slot][fed[slot]:fed[slot] + take]
        valid = np.zeros((S,), np.int32)
        valid[slot] = take
        fed[slot] += take
        p, state = app(state, jnp.asarray(chunk), jnp.asarray(valid))
        last_p[slot] = np.asarray(p[slot])
    for s in range(S):
        if fed[s] != total[s]:
            continue
        ref = np.asarray(pipe.apply(jnp.asarray(audio[s])[None]))[0]
        np.testing.assert_array_equal(last_p[s], ref,
                                      err_msg=f"seed={seed} slot={s}")


def test_fixed_zero_length_chunk_is_pure_readout():
    pipe, app = _fixed_pipe()
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 150))
    state = pipe.init_session(2)
    p1, state = app(state, x, jnp.full((2,), 150, jnp.int32))
    p0, state2 = app(state, jnp.zeros((2, 0)), jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_fixed_pallas_random_chunking_bitwise_matches_xla_and_one_shot(seed):
    """The int Pallas streaming kernel under random chunk partitions: every
    SessionState register tracks the int XLA step EXACTLY chunk-by-chunk,
    and the final registers/decisions equal the one-shot integer program —
    all gates are ==, in jit, in interpret mode on CPU."""
    from repro.core import fixed

    rng = np.random.default_rng(seed)
    px, appx = _fixed_pipe()
    pk, appk = _fixed_pipe(stream_impl="pallas")
    prog = px.fixed_program()
    S = 2
    lens, n = _partition(rng)
    x = jnp.asarray(rng.standard_normal((S, n)).astype(np.float32))
    p_q, _, s_q = fixed.infer_q(prog, fixed.quantize_signal(prog, x))
    p_one = prog.out_spec.dequantize(p_q)

    sx, sk = px.init_session(S), pk.init_session(S)
    p_x = p_k = None
    off = 0
    for ln in lens:
        ch = x[:, off:off + ln]
        off += ln
        v = jnp.full((S,), ln, jnp.int32)
        p_x, sx = appx(sx, ch, v)
        p_k, sk = appk(sk, ch, v)
        np.testing.assert_array_equal(
            np.asarray(p_x), np.asarray(p_k),
            err_msg=f"seed={seed}: int xla/pallas decisions diverged "
                    f"at {off}")
    _assert_states_bitwise(sx, sk, f"seed={seed} (fixed)")
    np.testing.assert_array_equal(np.asarray(sk.acc), np.asarray(s_q),
                                  err_msg=f"seed={seed}: acc vs one-shot")
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_one),
                                  err_msg=f"seed={seed}: decision vs "
                                          "one-shot")


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10 ** 9))
def test_fixed_pallas_slot_lifecycles_bitwise(seed):
    """Slot surgery through the int Pallas kernel: random open/feed/close
    schedules with garbage in non-fed rows — registers track the int XLA
    step exactly and completed slots equal their one-shot run."""
    rng = np.random.default_rng(seed)
    px, appx = _fixed_pipe()
    pk, appk = _fixed_pipe(stream_impl="pallas")
    S = 3
    total = [int(rng.integers(40, 200)) for _ in range(S)]
    audio = [rng.standard_normal(t).astype(np.float32) for t in total]
    fed = [0] * S
    sx, sk = px.init_session(S), pk.init_session(S)
    last_p = [None] * S
    for _ in range(15):
        slot = int(rng.integers(S))
        take = min(int(rng.choice(_LEN_MENU)), total[slot] - fed[slot])
        L = min((l for l in _LEN_MENU if l >= max(take, 1)),
                default=_LEN_MENU[-1])
        chunk = (rng.standard_normal((S, L)) * 50.0).astype(np.float32)
        chunk[slot, :take] = audio[slot][fed[slot]:fed[slot] + take]
        valid = np.zeros((S,), np.int32)
        valid[slot] = take
        fed[slot] += take
        p_x, sx = appx(sx, jnp.asarray(chunk), jnp.asarray(valid))
        p_k, sk = appk(sk, jnp.asarray(chunk), jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_k),
                                      err_msg=f"seed={seed}")
        last_p[slot] = np.asarray(p_k[slot])
    _assert_states_bitwise(sx, sk, f"seed={seed} (fixed lifecycles)")
    for s in range(S):
        if fed[s] != total[s]:
            continue
        ref = np.asarray(pk.apply(jnp.asarray(audio[s])[None]))[0]
        np.testing.assert_array_equal(last_p[s], ref,
                                      err_msg=f"seed={seed} slot={s}")


def test_fixed_pallas_multi_block_chunk_bitwise():
    """A 1,100-sample chunk spans three 512-sample blocks at octave 0 and
    two at octave 1, so the int kernel rebuilds its window scratch per
    block and reuses it across the filters of each. Slot 0's valid count
    ends mid-block, and its decimator phases are odd, even, odd (3 samples
    of silence consumed); slot 1 has 0 valid samples and even phases.
    Every register equals ``fixed.session_step_q``'s."""
    from repro.core import fixed
    from repro.kernels import fir_mp_stream_q

    pipe, _ = _fixed_pipe(stream_impl="pallas")
    prog = pipe.fixed_program()
    st = pipe.init_session(2)
    st = st._replace(consumed=tuple(
        c.at[0].set(k) for c, k in zip(st.consumed, (3, 2, 1))))
    n = jnp.asarray([777, 0], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 1100))
    pos = jnp.arange(x.shape[1])[None, :]
    xq = jnp.where(pos < n[:, None], fixed.quantize_signal(prog, x), 0)
    got = jax.jit(lambda q, s: fir_mp_stream_q(
        prog, q, n, s.delays, s.consumed, s.acc, s.amax))(xq, st)
    want = jax.jit(lambda q, s: fixed.session_step_q(prog, s, q, n)[0])(
        xq, st)
    for name, a, b in zip(("delays", "consumed", "acc", "amax"), got,
                          (want.delays, want.consumed, want.acc,
                           want.amax)):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                          err_msg=f"{name} diverged")


def test_fixed_stream_server_end_to_end(tmp_path):
    """StreamServer serves numerics='fixed': open/feed/split/evict/reopen,
    with the final decision per stream equal (exactly — same codes, same
    dequantization) to one-shot inference on the concatenated audio, and
    the int32 registers round-tripping the named-checkpoint store."""
    from repro.serving import StreamServer

    pipe, _ = _fixed_pipe()
    rng = np.random.default_rng(9)
    xa = rng.standard_normal(700).astype(np.float32)
    xb = rng.standard_normal(420).astype(np.float32)
    srv = StreamServer(pipe, capacity=2, max_chunk=256,
                       checkpoint_dir=str(tmp_path))
    assert srv.stats()["numerics"] == "fixed"
    assert srv.state.acc.dtype == jnp.int32
    srv.open("a")
    srv.open("b")
    out = []
    out += srv.feed([("a", xa[:300]), ("b", xb[:33])])
    out += srv.feed([("b", xb[33:420]), ("a", xa[300:301])])
    srv.evict("a")                      # parks int32 registers on disk
    srv.open("a")                       # restores them dtype-checked
    out += srv.feed([("a", xa[301:700])])
    final = {r.session_id: (r.label, r.confidence) for r in out}
    for sid, x in (("a", xa), ("b", xb)):
        p = np.asarray(pipe.apply(jnp.asarray(x)[None]))[0]
        assert final[sid] == (int(p.argmax()), float(p.max())), sid


def test_fixed_server_pallas_end_to_end_bitwise(tmp_path):
    """StreamServer serves numerics='fixed' + stream_impl='pallas'
    end-to-end (open/feed/split/evict/reopen): every result — label,
    confidence, samples_seen — and the final int32 registers equal the
    fixed XLA server's exactly."""
    from repro.serving import StreamServer

    rng = np.random.default_rng(9)
    xa = rng.standard_normal(700).astype(np.float32)
    xb = rng.standard_normal(420).astype(np.float32)
    results, accs = [], []
    for impl in ("xla", "pallas"):
        pipe, _ = _fixed_pipe() if impl == "xla" \
            else _fixed_pipe(stream_impl=impl)
        srv = StreamServer(pipe, capacity=2, max_chunk=256,
                           checkpoint_dir=str(tmp_path / impl))
        assert srv.stats()["numerics"] == "fixed"
        srv.open("a")
        srv.open("b")
        out = []
        out += srv.feed([("a", xa[:300]), ("b", xb[:33])])
        out += srv.feed([("b", xb[33:420]), ("a", xa[300:301])])
        srv.evict("a")                  # parks int32 registers on disk
        srv.open("a")                   # restores them dtype-checked
        out += srv.feed([("a", xa[301:700])])
        results.append([(r.session_id, r.label, r.confidence,
                         r.samples_seen) for r in out])
        accs.append(np.asarray(srv.state.acc))
    assert results[0] == results[1]
    np.testing.assert_array_equal(accs[0], accs[1])


def test_fixed_server_async_submit_drain_bitwise(tmp_path):
    """The async feed pipeline (PR 9) through the fixed-numerics server,
    BOTH stream impls: submits coalesced across evict/reopen churn and
    resolved by one drain() must equal the synchronous feed() path
    bit-for-bit — results AND the int32 registers."""
    from repro.serving import StreamServer, make_batched_step

    rng = np.random.default_rng(11)
    xa = rng.standard_normal(500).astype(np.float32)
    xb = rng.standard_normal(300).astype(np.float32)
    for impl in ("xla", "pallas"):
        pipe, _ = _fixed_pipe() if impl == "xla" \
            else _fixed_pipe(stream_impl=impl)
        step = make_batched_step(pipe)
        outs, accs = [], []
        for use_async in (False, True):
            srv = StreamServer(pipe, capacity=2, max_chunk=256,
                               checkpoint_dir=str(
                                   tmp_path / f"{impl}-{use_async}"),
                               step_fn=step)
            srv.open("a")
            srv.open("b")
            out = []
            if use_async:
                t1 = srv.submit([("a", xa[:300]), ("b", xb[:33])])
                t2 = srv.submit([("b", xb[33:200])])
                srv.drain()
                srv.evict("a")          # parks registers incl. queued work
                srv.open("a")
                t3 = srv.submit([("a", xa[300:500]),
                                 ("b", xb[200:300])])
                srv.drain()
                for t in (t1, t2, t3):
                    assert t.done
                    out += t.results
            else:
                out += srv.feed([("a", xa[:300]), ("b", xb[:33])])
                out += srv.feed([("b", xb[33:200])])
                srv.evict("a")
                srv.open("a")
                out += srv.feed([("a", xa[300:500]), ("b", xb[200:300])])
            outs.append([(r.session_id, r.label, r.confidence,
                          r.samples_seen) for r in out])
            accs.append(np.asarray(srv.state.acc))
        assert outs[0] == outs[1], impl
        np.testing.assert_array_equal(accs[0], accs[1],
                                      err_msg=f"{impl}: async registers")


def test_stream_server_pallas_bitwise_matches_xla_server(tmp_path):
    """End-to-end through StreamServer: open/feed/split/evict/reopen with
    the kernel hot path tracks the XLA server bit-for-bit."""
    from repro.serving import StreamServer

    px, pk = _pipes()
    rng = np.random.default_rng(5)
    xa = rng.standard_normal(700).astype(np.float32)
    xb = rng.standard_normal(420).astype(np.float32)
    results = []
    for pipe in (px, pk):
        srv = StreamServer(pipe, capacity=2, max_chunk=256,
                           checkpoint_dir=str(tmp_path / pipe.config.stream_impl))
        srv.open("a")
        srv.open("b")
        out = []
        out += srv.feed([("a", xa[:300]), ("b", xb[:33])])
        out += srv.feed([("b", xb[33:420]), ("a", xa[300:301])])
        srv.evict("a")
        srv.open("a")                    # restore from checkpoint
        out += srv.feed([("a", xa[301:700])])
        results.append([(r.session_id, r.label, r.confidence,
                         r.samples_seen) for r in out])
    assert results[0] == results[1]
