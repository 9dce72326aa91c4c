"""``bench/counts`` against the program's own operation census.

The census (``repro.analysis.census_jaxpr``) counts the ops of the traced
integer session step as it executes: every bisection step it runs and
every padded position. The counts take only the work the configuration's
arithmetic requires. Their ratio is recorded in PERF.md; here it is held
to the same order of magnitude, on the census's own smoke pipeline.
"""

import math

import pytest

from bench import counts

SMOKE = {
    "filterbank": {"fs": 4000, "num_octaves": 3, "filters_per_octave": 3,
                   "bp_taps": 16, "lp_taps": 6, "mode": "mp",
                   "gamma_f": 4.0, "solver": "newton"},
    "numerics": "fixed", "fixed_amax": 1.0,
    "classifier": {"num_classes": 10, "gamma1": 8.0, "weight_scale": 0.5},
}


def census_ratio() -> float:
    from repro.analysis import census_jaxpr, targets
    ts, _ = targets.build_targets(smoke=True)
    step = next(t for t in ts if t.name == "session_step_q")
    c = census_jaxpr(step.jaxpr)
    ours = counts.step_ops(SMOKE, {targets.CHUNK_LEN: 1})
    return (c["add"] + c["compare"] + c["shift"]) / ours


def test_counts_match_the_census_in_order():
    r = census_ratio()
    print(f"census / counts at smoke size: {r:.3f}")
    assert 0.5 <= r <= 4.0


@pytest.mark.parametrize("numerics", ["fixed", "float"])
def test_octave_work_halves(numerics):
    cfg = dict(SMOKE, numerics=numerics)
    if numerics == "float":          # Newton steps: a float-only setting
        cfg["filterbank"] = dict(SMOKE["filterbank"], solver_iters=12)
    lens = counts.octave_lengths(4096, 3)
    assert lens == [4096, 2048, 1024]
    ops = [counts.octave_ops(cfg, o, n) for o, n in enumerate(lens)]
    assert all(a > b for a, b in zip(ops, ops[1:]))
    calls = counts.kernel_calls(cfg, 8, 4096)
    assert [o for o, _ in calls] == [8 * x for x in ops]
    assert all(b > 8 * n * counts.WORD for (_, b), n in zip(calls, lens))


def test_fixed_solver_steps_are_the_least_bisection():
    p = counts.plan(SMOKE)
    it = counts._iters(SMOKE)
    assert it[0][0] == math.ceil(math.log2(p.bp[0].gamma))
