"""The float cell's timed path, the ``fir_mp_stream`` Pallas kernels, on
the CPU in interpret mode, against the plain float reference.

``esc10-float.backlog`` serves ``stream_impl: "pallas"``; the other CPU
tests of the float configuration run the XLA step. Here the ``tiny-float``
sizes are served through the Pallas step: a sound run is correct against
``bench/reference/float_ref.py``, and a run with each of ``test_faults``'s
broken steps is not. ``bench/counts`` counts the float work from the
configuration's ``solver_iters``, which the program never reads: it is tied
here to the Newton steps the served step runs.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util

from bench import counts, spec, system

from .conftest import load
from .test_faults import broken, run_tiny

FAULTS = ("unchanged", "half", "altered")
# the modules whose loops are MP solves: the solvers and the kernels' own
MP_MODULES = ("repro/core/mp.py", "repro/kernels/fir_mp.py")


def pallas(cell: dict) -> dict:
    return dict(cell, config=dict(cell["config"], stream_impl="pallas"))


def test_float_pallas_run_is_correct(tiny_cell):
    out = run_tiny(pallas(tiny_cell("float", "backlog")))
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_float_pallas_broken_step_is_not_correct(tiny_cell, fault):
    out = run_tiny(pallas(tiny_cell("float", "backlog")),
                   step=broken(fault))
    assert not out["correct"], out["check"]


def _mp_loop_lengths(jaxpr) -> list:
    """Trip counts of the MP solves' loops (a ``scan`` or ``while`` written
    in ``MP_MODULES``) in a jaxpr, nested jaxprs (jit, pallas_call, cond)
    included; a ``while`` reads None."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name in ("scan", "while"):
            frame = source_info_util.user_frame(e.source_info.traceback)
            path = frame.file_name.replace(os.sep, "/") if frame else ""
            if path.endswith(MP_MODULES):
                out.append(e.params.get("length"))
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _mp_loop_lengths(inner)
    return out


def test_counts_count_the_served_newton_steps():
    from repro.core.mp import DEFAULT_NEWTON_ITERS

    cfg = spec.config("esc10-mp-float32")
    assert cfg["filterbank"]["solver_iters"] == DEFAULT_NEWTON_ITERS
    assert set(counts._iters(cfg)) == {(DEFAULT_NEWTON_ITERS,) * 2}

    # every MP solve of the served Pallas step is one fixed-trip Newton loop
    # of that many steps: a band-pass and a low-pass solve in each octave
    # kernel (the last octave has no low-pass), and the readout's
    tiny = dict(load("tiny-float.json"), stream_impl="pallas")
    pipe = system.pipeline(tiny, system.weights(tiny, 2 ** 33 + 1))
    step = system.make_step(pipe, None)
    S, L = 4, int(tiny["server"]["max_chunk"])
    state = pipe.init_session(S, active=np.ones((S,), bool))
    jaxpr = jax.make_jaxpr(step)(pipe, state, jnp.zeros((S, L), jnp.float32),
                                 jnp.full((S,), L, jnp.int32))
    lengths = _mp_loop_lengths(jaxpr.jaxpr)
    octaves = int(tiny["filterbank"]["num_octaves"])
    assert lengths.count(DEFAULT_NEWTON_ITERS) >= 2 * (2 * octaves - 1) + 1
    assert set(lengths) == {DEFAULT_NEWTON_ITERS}
