"""Unified in-filter pipeline: audio in, class decisions out (paper Fig. 1).

``InFilterPipeline`` packs everything the deployed classifier needs —
filter-bank config, precomputed FIR taps, trained MP kernel-machine weights,
and the feature standardization statistics — into one pytree-serializable
object with ONE entry point:

* ``apply(x, state=None)``: the unified surface.

  - **Stateless** (``state=None``): one-shot ``audio (B, N) -> p (B, C)``.
    The whole multirate bank -> HWR/accumulate -> standardize -> MP kernel
    machine path traces as a single computation, so
    ``jax.jit(InFilterPipeline.apply)`` compiles the full audio->confidence
    graph in one unit (the "only classified data leaves the device"
    deployment mode). ``predict(x)`` remains as an alias.

  - **Stateful** (``state=`` a :class:`SessionState`): slot-batched
    streaming. The state packs S logical sensor streams ("slots") into
    stacked ``(S, ...)`` registers — per-octave FIR delay lines (the last
    ``max(bp_taps, lp_taps) - 1`` input samples), per-slot decimator phases
    (octave sample parities), running per-band accumulators, the running
    signal amax used for deployment-faithful quantization, per-slot sample
    counts, and a per-slot active mask. Feeding a chunk returns
    ``(p, state')``; arbitrarily long audio classifies in memory that does
    not grow with stream length — exactly the FPGA's zeroed-register
    streaming semantics, multiplexed S-wide.

Per-slot ``valid`` counts let one compiled call carry streams of different
chunk lengths (shorter rows are zero-padded and masked); a slot with zero
valid samples — or ``active=False`` — is provably inert: its registers are
bit-identical before and after the call, and it never perturbs other slots
(every op in the step is row-independent).

With ``quant_bits`` set, the chunk is quantized against the RUNNING amax
carried in the state (updated before scaling), matching the one-shot path's
per-stream amax semantics: once a stream's running amax equals its global
amax (e.g. the peak sits in the first chunk, or the state was seeded with a
calibrated ``amax``), streamed band outputs are bit-identical to the
one-shot deployment.

Chunk lengths may vary call-to-call (jit retraces per length — the serving
layer buckets lengths to powers of two to bound this); within a call the
octave-level valid lengths are data-dependent per-slot vectors handled with
masking + per-row dynamic slices, so the step is fully jit-able.

With ``config.numerics == "fixed"`` BOTH paths execute the bit-true int32
hardware twin (``repro.core.fixed``): the audio quantizes onto the static
calibrated ADC grid and every stage runs in add/sub/shift/compare integer
arithmetic, dequantizing only at the output surface. The session path
carries every register as an integer in the fixed-point grid (8-bit
octave-signal delay lines, 32-bit accumulators, running max |code|), and —
because the ADC grid is static and integer addition is associative —
chunked streaming decisions are bit-for-bit equal to one-shot ``apply(x)``
from the FIRST chunk, with no peak-seen caveat (docs/numerics.md). Both
stream impls stream fixed numerics: ``stream_impl="pallas"`` routes the
identical integer step through the VMEM-resident kernel
(``kernels.fir_mp_stream_q``) with bit-for-bit the same registers and
decisions. Note the program lowering is host-side, so ``jax.jit`` a closure
over a *concrete* pipeline (``jit(lambda x, st: pipe.apply(x, st))``) or
the compiled program (``prog = pipe.fixed_program(); jit(lambda x:
fixed.predict(prog, x))``) rather than ``InFilterPipeline.apply`` with the
pipeline as a traced pytree argument — that raises a TypeError with this
guidance.

Migration (PR 2): ``init_state``/``step``/``StreamingState`` — the one-
cohort streaming API — remain as thin shims over the session path and will
go away; new code should use ``init_session``/``apply``/``SessionState``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kernel_machine as km
from repro.core import filterbank as fbm
from repro.core import mp as mp_mod
from repro.core.filterbank import FilterBank, FilterBankConfig

__all__ = [
    "InFilterPipeline",
    "SessionState",
    "StreamingState",
    "clear_slots",
    "set_active",
    "reset_slot",
    "take_slot",
    "put_slot",
]


class SessionState(NamedTuple):
    """Slot-batched streaming registers: S logical streams, stacked (S, ...).

    delays:   per octave, (S, T-1) with T = max(bp_taps, lp_taps): the last
              T-1 samples of that octave's input signal (zeros at start —
              the FPGA's cleared register bank).
    consumed: per octave, (S,) int32: octave samples seen so far, per slot.
              Its parity is that slot's ÷2 decimator phase.
    acc:      (S, P) running renormalized per-band accumulators.
    amax:     (S,) running max |input| per slot — the symmetric quantization
              range under ``quant_bits`` (and free calibration telemetry
              without). Seed it via ``init_session(amax=...)`` for
              bit-faithful quantized streaming from the first chunk.
    count:    (S,) int32 input samples consumed per slot (== consumed[0];
              kept separately so serving code never reaches into octaves).
    active:   (S,) bool slot admission mask. Inactive slots are forced to
              zero valid samples, so they are inert no matter what the
              padded chunk rows contain.
    """
    delays: tuple
    consumed: tuple
    acc: jax.Array
    amax: jax.Array
    count: jax.Array
    active: jax.Array

    @property
    def capacity(self) -> int:
        return self.acc.shape[0]


class StreamingState(NamedTuple):
    """DEPRECATED one-cohort streaming state (pre-session API).

    Kept so existing ``init_state``/``step`` callers run unchanged; it is a
    view of :class:`SessionState` where all B streams share one age (scalar
    per-octave ``consumed``). ``amax`` is the per-stream running amax that
    now backs quantized streaming (the old chunk-local scaling is gone).
    """
    delays: tuple
    consumed: tuple
    acc: jax.Array
    amax: jax.Array


@jax.tree_util.register_pytree_node_class
class InFilterPipeline:
    """Config + taps + trained params + standardization in one pytree."""

    def __init__(self, config: FilterBankConfig, bp_taps: tuple,
                 lp_taps: tuple, mu: jax.Array, sigma: jax.Array,
                 clf: km.MPKernelMachineParams):
        self.config = config
        self.bp_taps = tuple(bp_taps)    # per octave: (F, M)
        self.lp_taps = tuple(lp_taps)    # per ÷2 stage: (M_lp,)
        self.mu = mu                     # (P,)
        self.sigma = sigma               # (P,)
        self.clf = clf
        self._fixed_prog = None          # lazy compile_pipeline cache

    # -- pytree protocol (config is static aux data; arrays are leaves) ----

    def tree_flatten(self):
        children = (self.bp_taps, self.lp_taps, self.mu, self.sigma, self.clf)
        return children, self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(config, *children)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_filterbank(cls, fb: FilterBank, clf: km.MPKernelMachineParams,
                        mu: jax.Array, sigma: jax.Array) -> "InFilterPipeline":
        return cls(fb.config, fb.bp_by_octave, fb.lp_filters,
                   jnp.asarray(mu), jnp.asarray(sigma), clf)

    @classmethod
    def fit(cls, config: FilterBankConfig, x_train, y_train,
            num_classes: int, train_cfg=None):
        """Extract features, standardize, train the MP kernel machine, and
        pack the deployable pipeline. Returns (pipeline, loss_trace)."""
        from repro.core import trainer  # lazy: trainer pulls in optimizers
        if train_cfg is None:
            train_cfg = trainer.TrainConfig()
        fb = FilterBank(config)
        x_train = jnp.asarray(x_train)
        s = jax.jit(fb.accumulate)(x_train)
        mu = jnp.mean(s, axis=0)
        sigma = jnp.std(s, axis=0, ddof=1) + 1e-6
        K = (s - mu) / sigma
        params, losses = trainer.train(K, jnp.asarray(y_train), num_classes,
                                       train_cfg)
        return cls.from_filterbank(fb, params, mu, sigma), losses

    # -- unified entry point -------------------------------------------------

    def apply(self, x: jax.Array, state: SessionState | None = None, *,
              valid: jax.Array | None = None, return_features: bool = False):
        """The one inference surface: stateless one-shot or stateful session.

        Stateless (``state=None``): ``x (B, N) -> p (B, C)`` signed per-class
        confidence in [-1, 1]; with ``return_features=True`` returns
        ``(p, phi)`` where ``phi (B, P)`` is the standardized kernel vector.

        Stateful: ``x (S, L)`` is one chunk per slot of ``state`` (use zeros
        for slots with nothing to feed and pass per-slot ``valid`` sample
        counts; ``None`` means every row is fully valid). Returns
        ``(p, state')`` — note output-first, unlike the deprecated ``step``
        — or ``(p, phi, state')`` with ``return_features=True``. ``p`` is
        each slot's decision from all evidence so far.

        Numerics and the parity guarantee: with ``numerics="float"``
        (default) both paths run the f32 engine; streamed decisions match
        one-shot to f32 round-off, bit-for-bit when the whole signal fits
        one call, and under ``quant_bits`` bit-for-bit once the running
        amax has seen the stream's peak. With ``numerics="fixed"`` both
        paths run the bit-true int32 hardware twin and streamed decisions
        (and every register) are bit-for-bit equal to one-shot ``apply(x)``
        under ANY chunking, from the first chunk — the ADC grid is static
        and integer addition is associative (docs/numerics.md).
        """
        x = jnp.asarray(x)
        if state is None:
            if self.config.numerics == "fixed":
                from repro.core import fixed
                p, phi = fixed.predict(
                    self.fixed_program(), x,
                    use_pallas=self.config.use_pallas)
                return (p, phi) if return_features else p
            phi = self.features(x)
            p = km.forward(self.clf, phi, exact=False)
            return (p, phi) if return_features else p
        if isinstance(state, StreamingState):
            raise TypeError(
                "apply() takes a SessionState (init_session); for the "
                "deprecated one-cohort StreamingState keep using step(), or "
                "migrate: state = pipe.init_session(S); p, state = "
                "pipe.apply(chunk, state)")
        if x.ndim != 2 or x.shape[0] != state.capacity:
            raise ValueError(
                f"chunk shape {x.shape} does not match session capacity "
                f"{state.capacity}: expected ({state.capacity}, L)")
        if valid is None:
            valid = jnp.full((state.capacity,), x.shape[1], jnp.int32)
        state, p, phi = self._session_step(state, x, valid)
        if return_features:
            return p, phi, state
        return p, state

    # -- one-shot ------------------------------------------------------------

    @property
    def num_bands(self) -> int:
        return self.config.num_filters

    def features(self, x: jax.Array,
                 amax: jax.Array | None = None) -> jax.Array:
        """audio (B, N) -> standardized kernel vector Phi (B, P).

        Under ``quant_bits`` the signal is quantized per stream row (scale =
        that row's amax, or the explicit ``amax`` override), matching the
        session streaming path's running-amax semantics. With
        ``numerics='fixed'`` this dequantizes the integer path's 8-bit
        standardized kernel vector instead (pow2-snapped sigma; see
        ``repro.core.fixed``).
        """
        if self.config.numerics == "fixed":
            if amax is not None:
                # the fixed program quantizes on its STATIC calibrated ADC
                # grid; silently dropping a per-call amax override would
                # hand back wrong-scale features
                raise ValueError(
                    "features(amax=...) has no effect under "
                    "numerics='fixed' — the ADC full-scale is the static "
                    "config.fixed_amax / fixed_program(amax=...) "
                    "calibration")
            from repro.core import fixed
            prog = self.fixed_program()
            _, phi_q, _ = fixed.infer_q(
                prog, fixed.quantize_signal(prog, x),
                use_pallas=self.config.use_pallas)
            return prog.phi.dequantize(phi_q)
        s = fbm.multirate_accumulate(x, self.bp_taps, self.lp_taps,
                                     self.config, amax=amax)
        return (s - self.mu) / self.sigma

    def fixed_program(self, **overrides):
        """The compiled integer program for this pipeline (lazy, cached for
        the no-override call — the program ``apply``/``features`` and the
        session streaming path execute). ``overrides`` pass through to
        ``repro.core.fixed.compile_pipeline`` (amax, signal_bits,
        internal_bits, phi_amax, octave_gains, calibration_audio) and
        return a fresh, UNcached program; use :meth:`calibrate_fixed` to
        make a calibrated program the pinned one."""
        from repro.core import fixed
        if overrides:
            return fixed.compile_pipeline(self, **overrides)
        if self._fixed_prog is None:
            self._fixed_prog = fixed.compile_pipeline(self)
        return self._fixed_prog

    def calibrate_fixed(self, calibration_audio, **overrides):
        """Compile the integer program calibrated on ``calibration_audio``
        (ADC full-scale + per-octave register pre-gains) and PIN it as this
        pipeline's cached program, so one-shot ``apply``/``features`` AND
        the integer session-streaming path all execute the calibrated
        datapath. Returns the program."""
        from repro.core import fixed
        self._fixed_prog = fixed.compile_pipeline(
            self, calibration_audio=calibration_audio, **overrides)
        return self._fixed_prog

    def predict(self, x: jax.Array) -> jax.Array:
        """audio (B, N) -> signed per-class confidence p (B, C) in [-1, 1].

        Alias for stateless ``apply(x)``."""
        return self.apply(x)

    # -- session streaming ---------------------------------------------------

    @property
    def _delay_len(self) -> int:
        return max(self.config.bp_taps, self.config.lp_taps) - 1

    def init_session(self, capacity: int, dtype=jnp.float32, *,
                     amax: jax.Array | float | None = None,
                     active: jax.Array | None = None) -> SessionState:
        """Fresh slot-batched state for ``capacity`` logical streams.

        ``amax`` pre-seeds the running quantization range (scalar or (S,)
        — e.g. a calibrated ADC full-scale) so quantized streaming is
        bit-faithful from the first chunk. ``active`` sets the admission
        mask (default: all slots active; a StreamServer starts all-inactive
        and admits via open()).

        With ``numerics="fixed"`` every register is an integer on the
        fixed-point grid (``dtype`` is ignored): delay lines hold 8-bit
        octave-signal codes, ``acc`` the 32-bit accumulators, and ``amax``
        the running max |ADC code| — telemetry only, since the ADC grid is
        static (a float ``amax`` seed is converted to codes)."""
        c = self.config
        T1 = self._delay_len
        if c.numerics == "fixed":
            dtype = jnp.int32
        if amax is None:
            amax_arr = jnp.zeros((capacity,), dtype)
        elif c.numerics == "fixed":
            amax_arr = jnp.broadcast_to(
                self.fixed_program().signal.quantize(jnp.abs(
                    jnp.asarray(amax, jnp.float32))), (capacity,))
        else:
            amax_arr = jnp.broadcast_to(
                jnp.asarray(amax, dtype), (capacity,))
        if active is None:
            active_arr = jnp.ones((capacity,), bool)
        else:
            active_arr = jnp.asarray(active, bool)
        return SessionState(
            delays=tuple(jnp.zeros((capacity, T1), dtype)
                         for _ in range(c.num_octaves)),
            consumed=tuple(jnp.zeros((capacity,), jnp.int32)
                           for _ in range(c.num_octaves)),
            acc=jnp.zeros((capacity, c.num_filters), dtype),
            amax=amax_arr,
            count=jnp.zeros((capacity,), jnp.int32),
            active=active_arr,
        )

    def _session_step(self, state: SessionState, chunk: jax.Array,
                      valid: jax.Array):
        """Consume one (S, L) slot-batched chunk with per-slot valid counts.

        Returns (state', p (S, C), phi (S, P)). Every operation is row-
        independent, and rows with zero valid samples keep bit-identical
        registers (delay slice at offset 0 re-reads the old delays; masked
        HWR sums vanish), which is what makes padding slots inert.

        ``config.stream_impl`` selects the octave-cascade hot path
        (``_cascade``): "xla" splices [delay, chunk] per octave in XLA;
        "pallas" runs the stateful streaming kernel (``kernels.fir_mp_stream``
        in float, ``kernels.fir_mp_stream_q`` in fixed), which carries the
        delay lines / accumulators / running amax in VMEM scratch across its
        chunk-block grid. Both run the same solver math in the same blocked
        accumulation order, so they agree bit-for-bit.

        The step's ops carry the named scopes ``session_step`` and, inside
        it, ``quantize``, ``octave_cascade`` and ``readout`` (in both
        numerics), which the profiler reports with each device op.
        """
        with jax.named_scope("session_step"):
            if self.config.numerics == "fixed":
                return self._session_step_fixed(state, chunk, valid)
            return self._session_step_float(state, chunk, valid)

    def _session_step_float(self, state: SessionState, chunk: jax.Array,
                            valid: jax.Array):
        S, L = chunk.shape
        n = jnp.where(state.active, jnp.asarray(valid, jnp.int32), 0)
        if L > 0:
            pos0 = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
            chunk = jnp.where(pos0 < n[:, None], chunk, 0)
        state = self._cascade(state, chunk, n)
        with jax.named_scope("readout"):
            phi = (state.acc - self.mu) / self.sigma
            return state, km.forward(self.clf, phi, exact=False), phi

    def _session_step_fixed(self, state: SessionState, chunk: jax.Array,
                            valid: jax.Array):
        """The int32 session step: quantize the chunk onto the static ADC
        grid, zero invalid positions, and run the integer step on the codes
        — every register stays on the fixed-point grid and chunked
        decisions are bit-for-bit the one-shot program's."""
        from repro.core import fixed
        prog = self.fixed_program()
        S, L = chunk.shape
        n = jnp.where(state.active, jnp.asarray(valid, jnp.int32), 0)
        if L == 0:
            xq = jnp.zeros((S, 0), jnp.int32)
        else:
            with jax.named_scope("quantize"):
                xq = fixed.quantize_signal(prog, chunk)
                pos0 = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
                xq = jnp.where(pos0 < n[:, None], xq, 0)
        state, p_q, phi_q = self._session_step_q(state, xq, n)
        with jax.named_scope("readout"):
            return state, prog.out_spec.dequantize(p_q), \
                prog.phi.dequantize(phi_q)

    def _session_step_q(self, state: SessionState, xq: jax.Array,
                        n: jax.Array):
        """The integer step on ADC codes, ``fixed.session_step_q`` through
        this pipeline's ``stream_impl``: the octave cascade, then the
        integer readout. Returns ``(state', p_q, phi_q)``; the registers
        and codes are bit-identical under either impl."""
        from repro.core import fixed
        state = self._cascade(state, xq, n)
        p_q, phi_q = fixed.readout_q(self.fixed_program(), state.acc)
        return state, p_q, phi_q

    def _cascade(self, state: SessionState, chunk: jax.Array,
                 n: jax.Array) -> SessionState:
        """The octave cascade over one chunk (samples in float, ADC codes
        in fixed), through the hot path ``config.stream_impl`` picks: "xla"
        (``_cascade_xla``, or ``fixed.cascade_q``) or "pallas"
        (``_cascade_pallas``). A zero-length chunk moves no register."""
        from repro.core import fixed
        c = self.config
        if c.stream_impl not in ("xla", "pallas"):
            # a typo must not silently serve XLA results as "the kernel"
            raise ValueError(f"unknown stream_impl {c.stream_impl!r}: "
                             "expected 'xla' or 'pallas'")
        if c.stream_impl == "pallas" and c.mode != "mp":
            raise ValueError(
                f"stream_impl='pallas' runs the MP streaming kernel; it has "
                f"no {c.mode!r}-mode variant (use stream_impl='xla')")
        if chunk.shape[1] == 0:
            return state
        with jax.named_scope("octave_cascade"):
            if c.stream_impl == "pallas":
                return self._cascade_pallas(state, chunk, n)
            if c.numerics == "fixed":
                return fixed.cascade_q(self.fixed_program(), state, chunk, n)
            return self._cascade_xla(state, chunk, n)

    def _cascade_pallas(self, state: SessionState, chunk: jax.Array,
                        n: jax.Array) -> SessionState:
        """Octave cascade through the stateful Pallas streaming kernel:
        ``kernels.fir_mp_stream_q`` on the integer codes in fixed numerics,
        ``kernels.fir_mp_stream`` in float."""
        from repro.kernels import fir_mp_stream, fir_mp_stream_q
        c = self.config
        if c.numerics == "fixed":
            delays, consumed, acc, amax = fir_mp_stream_q(
                self.fixed_program(), chunk, n, state.delays, state.consumed,
                state.acc, state.amax)
        else:
            if c.quant_bits is not None:
                # quantization needs the post-update running amax BEFORE
                # the filter pass, so it cannot fold into the kernel's
                # single sweep
                with jax.named_scope("quantize"):
                    amax = jnp.maximum(state.amax,
                                       jnp.max(jnp.abs(chunk), axis=-1))
                    chunk = fbm.quant_signal(chunk, c, amax=amax)
                update_amax = False
            else:
                # raw path: the octave-0 kernel folds the running-amax
                # update into its grid sweep (one HBM read serves filter
                # AND calibrate)
                amax = state.amax
                update_amax = True
            delays, consumed, acc, amax = fir_mp_stream(
                chunk, n, state.delays, state.consumed, state.acc, amax,
                self.bp_taps, self.lp_taps, c.gamma_f, solver=c.solver,
                update_amax=update_amax)
        return state._replace(delays=delays, consumed=consumed, acc=acc,
                              amax=amax, count=state.count + n)

    def _cascade_xla(self, state: SessionState, chunk: jax.Array,
                     n: jax.Array) -> SessionState:
        """Octave cascade in XLA: per-octave [delay, chunk] splice."""
        c = self.config
        S, L = chunk.shape
        # running amax update precedes scaling: chunk i is quantized against
        # max over chunks 0..i, converging to the one-shot global scale
        amax = jnp.maximum(state.amax, jnp.max(jnp.abs(chunk), axis=-1))
        if c.quant_bits is not None:
            with jax.named_scope("quantize"):
                chunk = fbm.quant_signal(chunk, c, amax=amax)
        T1 = self._delay_len
        M_bp, M_lp = c.bp_taps, c.lp_taps
        x_o, n_o = chunk, n
        l_max = L                          # static per-call octave capacity
        delays, consumed, parts = [], [], []
        for o in range(c.num_octaves):
            # splice the delay-line registers in front of the chunk; in-chunk
            # sample p sits at buf position T1 + p with its full FIR history.
            # Valid-mode FIR on the trailing window skips the T1 prefix
            # solves the padded form would compute and throw away — the
            # kept positions are bitwise the same.
            buf = jnp.concatenate([state.delays[o], x_o], axis=1)
            y = fbm.bank_fir_valid(buf[:, T1 - (M_bp - 1):],
                                   self.bp_taps[o], c)       # (S, F, l_max)
            # blocked HWR accumulation: the shared reduction order that
            # keeps this path bit-identical to one-shot accumulate (single
            # chunk) and to the Pallas streaming kernel's grid-carried sums
            parts.append(fbm.hwr_accumulate(y, n_o[:, None])
                         * (2.0 ** o))                           # (S, F)
            # register update: the last T1 *valid* samples become the new
            # delay line — per-slot offsets, so vmap the dynamic slice
            delays.append(jax.vmap(
                lambda b, s: jax.lax.dynamic_slice_in_dim(b, s, T1, axis=0)
            )(buf, n_o))
            consumed.append(state.consumed[o] + n_o)
            if o < c.num_octaves - 1:
                # ÷2 decimator: keep even GLOBAL indices. The first kept
                # in-chunk index is each slot's stream-parity phase.
                start = jnp.remainder(state.consumed[o], 2)       # (S,)
                l_next = (l_max + 1) // 2
                buf_lp = buf[:, T1 - (M_lp - 1):]
                if c.mode == "mp" and not c.use_pallas:
                    # solve ONLY the kept positions: per-slot stride-2
                    # window gather (kept sample k of slot s ends at
                    # buf_lp[s, start_s + 2k + M_lp - 1]); halves the LP
                    # solve count vs filter-then-discard, bit-identically.
                    buf_lp = jnp.pad(buf_lp, ((0, 0), (0, 1)))
                    widx = (2 * jnp.arange(l_next)[:, None]
                            + jnp.arange(M_lp)[None, :])   # (l_next, M_lp)
                    win = jax.vmap(lambda r, s: r[s + widx])(buf_lp, start)
                    kept = mp_mod._mp_dot_fast(
                        win, self.lp_taps[o][::-1], c.gamma_f, c.solver)
                else:
                    y_lp = fbm.single_fir_valid(buf_lp, self.lp_taps[o],
                                                c)        # (S, l_max)
                    y_pad = jnp.pad(y_lp,
                                    ((0, 0), (0, 2 * l_next + 1 - l_max)))
                    kept = jax.vmap(
                        lambda r, s: jax.lax.dynamic_slice_in_dim(
                            r, s, 2 * l_next, axis=0)
                    )(y_pad, start)[:, ::2]
                x_o = kept                                        # (S, l_next)
                n_o = jnp.maximum(0, (n_o - start + 1) // 2)
                l_max = l_next
        acc = state.acc + jnp.concatenate(parts, axis=-1)
        return SessionState(tuple(delays), tuple(consumed), acc, amax,
                            state.count + n, state.active)

    # -- deprecated one-cohort streaming shims -------------------------------

    def init_state(self, batch: int, dtype=jnp.float32) -> StreamingState:
        """DEPRECATED: use ``init_session``. One cohort of ``batch`` streams
        that all advance in lockstep (scalar per-octave ages)."""
        sess = self.init_session(batch, dtype)
        return StreamingState(
            delays=sess.delays,
            consumed=tuple(jnp.zeros((), jnp.int32) for _ in sess.consumed),
            acc=sess.acc,
            amax=sess.amax,
        )

    def step(self, state: StreamingState,
             chunk: jax.Array) -> tuple[StreamingState, jax.Array]:
        """DEPRECATED: use ``apply``. Consume one (B, L) chunk; return
        (state', p (B, C)).

        Thin shim over the session step: lifts the cohort state to a
        SessionState (broadcast ages), runs the unified path, and collapses
        back (all rows advance by the same chunk, so ages stay uniform).
        p matches ``predict`` over the concatenated signal to f32 round-off;
        under ``quant_bits`` the running-amax state quantizes exactly like
        one-shot deployment once the stream's peak has been seen.
        """
        chunk = jnp.asarray(chunk)
        B = chunk.shape[0]
        sess = SessionState(
            delays=state.delays,
            consumed=tuple(jnp.broadcast_to(cns, (B,)).astype(jnp.int32)
                           for cns in state.consumed),
            acc=state.acc,
            amax=state.amax,
            count=jnp.broadcast_to(state.consumed[0], (B,)).astype(jnp.int32),
            active=jnp.ones((B,), bool),
        )
        sess, p, _ = self._session_step(
            sess, chunk, jnp.full((B,), chunk.shape[1], jnp.int32))
        state = StreamingState(sess.delays,
                               tuple(cns[0] for cns in sess.consumed),
                               sess.acc, sess.amax)
        return state, p

    def stream(self, chunks, *, dtype=None) -> jax.Array:
        """Convenience: classify an iterable of (B, L_i) chunks; returns the
        final p. Memory stays fixed regardless of total stream length.

        ``dtype`` fixes the state/register dtype up front (``None``: the
        first chunk's dtype). Chunks whose dtype disagrees raise instead of
        letting XLA silently upcast the registers mid-stream.
        """
        state = None
        p = None
        for chunk in chunks:
            chunk = jnp.asarray(chunk)
            if dtype is None:
                dtype = chunk.dtype
            if chunk.dtype != jnp.dtype(dtype):
                raise ValueError(
                    f"stream() chunk dtype {chunk.dtype} != stream dtype "
                    f"{jnp.dtype(dtype)}; cast explicitly (mixed-dtype "
                    "chunks would silently upcast the streaming registers)")
            if state is None:
                state = self.init_state(chunk.shape[0], dtype)
            state, p = self.step(state, chunk)
        if p is None:
            raise ValueError("stream() needs at least one chunk")
        return p


# ---------------------------------------------------------------------------
# slot surgery helpers (host-side admission bookkeeping for serving code)
# ---------------------------------------------------------------------------


def clear_slots(state: SessionState, slots) -> SessionState:
    """Zero the per-stream registers of ``slots`` (fresh-tenant admission:
    a reused slot must not leak the previous stream). Leaves ``active``
    untouched — pair with :func:`set_active`."""
    slots = jnp.asarray(slots)
    return SessionState(
        delays=tuple(d.at[slots].set(0) for d in state.delays),
        consumed=tuple(cns.at[slots].set(0) for cns in state.consumed),
        acc=state.acc.at[slots].set(0),
        amax=state.amax.at[slots].set(0),
        count=state.count.at[slots].set(0),
        active=state.active,
    )


def set_active(state: SessionState, slots, value) -> SessionState:
    """Flip the admission mask for ``slots`` (``value`` may be traced)."""
    return state._replace(
        active=state.active.at[jnp.asarray(slots)].set(
            jnp.asarray(value, bool)))


def reset_slot(state: SessionState, slot, active) -> SessionState:
    """Zero one slot's registers and set its admission flag:
    :func:`clear_slots` then :func:`set_active`. ``slot`` and ``active``
    may be traced, so one jitted program serves every slot, admission
    (``active`` True) and release (False) alike."""
    slots = jnp.reshape(jnp.asarray(slot), (1,))
    return set_active(clear_slots(state, slots), slots, active)


def take_slot(state: SessionState, slot: int) -> SessionState:
    """Extract one slot's registers as an unbatched row tree (for
    checkpointing an evicted session)."""
    return jax.tree.map(lambda a: a[slot], state)


def put_slot(state: SessionState, slot: int, row: SessionState) -> SessionState:
    """Insert a row tree (from :func:`take_slot`) back into ``slot``."""
    return jax.tree.map(lambda a, r: a.at[slot].set(r), state, row)
