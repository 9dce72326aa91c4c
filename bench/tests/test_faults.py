"""The whole run, with the timed path broken underneath, comes out not
correct: the harness's look for a chip is skipped and everything else
(router, loops, the window, the check) runs as on the chip, on the CPU at
the ``data/tiny-*`` sizes. One run per fault each cell can have:

* ``unchanged``: the step returns its state unchanged (no slot advances);
* ``half``: half of the batch is left out (the upper slots never advance);
* ``altered``: the decision is altered where the step produces it.

No cell has an exchange between chips to leave out: the slot-sharded
step holds no collective.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import run, system


def broken(fault: str):
    def make(pipe, mesh):
        step = system.make_step(pipe, mesh)

        def faulty(pipe_, state, chunk, valid):
            if fault == "unchanged":
                valid = jnp.zeros_like(valid)
            elif fault == "half":
                keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
                valid = jnp.where(keep, valid, 0)
            state, p = step(pipe_, state, chunk, valid)
            if fault == "altered":
                p = p.at[:, 0].add(0.5)
            return state, p
        return faulty
    return make


def run_tiny(cell, step=None, seed=987_654_321_012):
    return run.run_cell(cell, seed, 0.3, False, time.perf_counter(),
                        jax.devices()[:1], step=step)


@pytest.mark.parametrize("config,traffic", [
    ("fixed", "backlog"), ("fixed", "live"), ("float", "backlog")])
def test_sound_run_is_correct(tiny_cell, config, traffic):
    out = run_tiny(tiny_cell(config, traffic))
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("config,traffic,fault", [
    ("fixed", "backlog", "unchanged"), ("fixed", "backlog", "half"),
    ("fixed", "backlog", "altered"), ("fixed", "live", "altered"),
    ("float", "backlog", "unchanged"), ("float", "backlog", "half"),
    ("float", "backlog", "altered")])
def test_broken_step_is_not_correct(tiny_cell, config, traffic, fault):
    out = run_tiny(tiny_cell(config, traffic), step=broken(fault))
    assert not out["correct"], out["check"]
