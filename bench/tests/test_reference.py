"""The plain references against the program, and their controls.

At a size a CPU test holds (the ``data/tiny-*`` configurations), the
fixed reference is bit for bit the program's one-shot integer program and
the float reference lies within the configuration's limits of the
program's one-shot float path. The control, the reference computed one
precision step lower (4-bit signals and weights for the int8 datapath,
bfloat16 for float32), must fail those same limits.
"""

import numpy as np
import pytest

from bench import compare, system

from .conftest import load


def audio(seed: int, lengths):
    g = np.random.default_rng(seed)
    x = g.standard_normal((len(lengths), max(lengths))).astype(np.float32)
    for b, n in enumerate(lengths):
        x[b, n:] = 0
    return x, np.asarray(lengths)


def program(cfg, w, x, lengths):
    """The program's one-shot answer per stream, as real values."""
    import jax.numpy as jnp
    pipe = system.pipeline(cfg, w)
    accs, ps = [], []
    for b, n in enumerate(lengths):
        xb = jnp.asarray(x[b:b + 1, :n])
        if cfg["numerics"] == "fixed":
            from repro.core import fixed
            prog = pipe.fixed_program()
            p_q, _, s_q = fixed.infer_q(prog, fixed.quantize_signal(prog, xb))
            accs.append(np.asarray(prog.bank.acc.dequantize(s_q))[0])
            ps.append(np.asarray(prog.out_spec.dequantize(p_q))[0])
        else:
            p, phi = pipe.apply(xb, return_features=True)
            accs.append(np.asarray(phi)[0] * w["sigma"] + w["mu"])
            ps.append(np.asarray(p)[0])
    labels = [int(np.argmax(p)) for p in ps]
    return {"acc": np.stack(accs), "label": labels,
            "confidence": [float(p[k]) for p, k in zip(ps, labels)]}


@pytest.mark.parametrize("name", ["tiny-fixed", "tiny-float"])
def test_reference_matches_the_program_and_the_control_does_not(name):
    cfg = load(f"{name}.json")
    w = system.weights(cfg, 2 ** 31 + 3)
    x, lengths = audio(1, [2500, 1700])
    ref = compare.reference(cfg, w, x, lengths)
    lim = compare.limits(cfg)
    got = compare.numbers(cfg, program(cfg, w, x, lengths), ref)
    assert compare.verdict(got, lim), got
    ctl = compare.reference(cfg, w, x, lengths, control=True)
    bad = compare.numbers(cfg, compare.control_served(ctl), ref)
    assert not compare.verdict(bad, lim), bad
