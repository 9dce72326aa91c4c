"""The system under test, built from a configuration file and a seed.

The harness makes the weights itself, on the device in one jitted call
from the seed, and hands them to the program's own constructors: the
classifier ROMs w+ / w- uniform in [0, ``weight_scale``), zero biases,
and the configuration's standardisation. The filter taps are the
program's own design from the configuration's sizes. The program is
served through its one entry point, ``StreamRouter``, with one shard whose
slots span the cell's chips.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench import loadgen


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _classifier(key, bands: int, classes: int, scale: float):
    k1, k2 = jax.random.split(key)
    return (jax.random.uniform(k1, (bands, classes)) * scale,
            jax.random.uniform(k2, (bands, classes)) * scale)


def weights(cfg: dict, seed: int) -> dict:
    """Every weight the served pipeline and the reference use, as host
    float32 arrays."""
    fb, clf = cfg["filterbank"], cfg["classifier"]
    bands = int(fb["num_octaves"]) * int(fb["filters_per_octave"])
    classes = int(clf["num_classes"])
    key = jax.random.PRNGKey(
        int(loadgen.rng(seed, "weights").integers(0, 2 ** 31 - 1)))
    wp, wn = _classifier(key, bands, classes, float(clf["weight_scale"]))
    std = cfg["standardize"]
    return {
        "w_pos": np.asarray(wp, np.float32), "w_neg": np.asarray(wn,
                                                                 np.float32),
        "b_pos": np.zeros(classes, np.float32),
        "b_neg": np.zeros(classes, np.float32),
        "mu": np.full(bands, float(std["mu"]), np.float32),
        "sigma": np.full(bands, float(std["sigma"]), np.float32),
    }


def pipeline(cfg: dict, w: dict):
    """The program's ``InFilterPipeline`` at the configuration's sizes."""
    import jax.numpy as jnp

    from repro.core import kernel_machine as km
    from repro.core.filterbank import FilterBank, FilterBankConfig
    from repro.core.pipeline import InFilterPipeline

    fb = cfg["filterbank"]
    fbc = FilterBankConfig(
        fs=float(fb["fs"]), num_octaves=int(fb["num_octaves"]),
        filters_per_octave=int(fb["filters_per_octave"]),
        bp_taps=int(fb["bp_taps"]), lp_taps=int(fb["lp_taps"]),
        mode=fb["mode"], gamma_f=float(fb["gamma_f"]), solver=fb["solver"],
        stream_impl=cfg["stream_impl"], numerics=cfg["numerics"],
        fixed_amax=float(cfg.get("fixed_amax", 1.0)))
    clf = km.MPKernelMachineParams(
        w_pos=jnp.asarray(w["w_pos"]), w_neg=jnp.asarray(w["w_neg"]),
        b_pos=jnp.asarray(w["b_pos"]), b_neg=jnp.asarray(w["b_neg"]),
        log_gamma1=jnp.log(jnp.float32(cfg["classifier"]["gamma1"])))
    pipe = InFilterPipeline.from_filterbank(
        FilterBank(fbc), clf, jnp.asarray(w["mu"]), jnp.asarray(w["sigma"]))
    return pipe


def make_step(pipe, mesh):
    """The donated session step the router's shard runs (tests wrap it to
    plant faults)."""
    from repro.serving import make_batched_step
    return make_batched_step(pipe, mesh)


def router(cfg: dict, pipe, capacity: int, chips: int, step=make_step):
    """A one-shard ``StreamRouter`` with ``capacity`` slots over ``chips``
    devices (a slot-sharded mesh when there are several)."""
    from repro.serving import StreamRouter

    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=chips, model=1)
    srv = cfg["server"]
    return StreamRouter(pipe, num_shards=1, capacity=capacity,
                        step_fn=step(pipe, mesh), mesh=mesh,
                        max_chunk=int(srv["max_chunk"]),
                        min_chunk=int(srv["min_chunk"]))
