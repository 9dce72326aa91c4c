"""Sharding rules, data pipeline, monitor, compression, and a subprocess
mini dry-run on 8 virtual devices."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.data.tokens import TokenStream
from repro.distributed import sharding as sh
from repro.distributed.monitor import StragglerMonitor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestShardingRules:
    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"))

    def test_param_specs_by_name(self):
        mesh = self._mesh()
        params = {
            "tok_embed": jax.ShapeDtypeStruct((512, 64), jnp.float32),
            "layers": {"attn": {"wq": jax.ShapeDtypeStruct((4, 64, 64),
                                                           jnp.float32)},
                       "norm1": {"scale": jax.ShapeDtypeStruct((64,),
                                                               jnp.float32)}},
        }
        specs = sh.param_specs(params, mesh)
        assert specs["tok_embed"] == P("model", "data")
        assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
        assert specs["layers"]["norm1"]["scale"] == P()

    def test_sanitize_drops_indivisible(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # axis size 1 divides everything -> kept
        assert sh.sanitize(("data", "model"), (7, 13), mesh) == P("data", "model")

    def test_batch_specs(self):
        mesh = self._mesh()
        batch = {"tokens": jax.ShapeDtypeStruct((8, 128), jnp.int32)}
        specs = sh.batch_specs(batch, mesh)
        assert specs["tokens"] == P(("data",), None)

    def test_moe_expert_weights_sharded_on_trailing(self):
        mesh = self._mesh()
        params = {"ffn": {"wi_gate": jax.ShapeDtypeStruct((3, 8, 16, 32),
                                                          jnp.float32)}}
        specs = sh.param_specs(params, mesh)
        assert specs["ffn"]["wi_gate"] == P(None, None, "data", "model")


class TestTokenStream:
    def test_deterministic(self):
        a = TokenStream(1000, 32, 8, seed=3).batch(7)
        b = TokenStream(1000, 32, 8, seed=3).batch(7)
        np.testing.assert_array_equal(a, b)

    def test_steps_differ(self):
        s = TokenStream(1000, 32, 8, seed=3)
        assert not np.array_equal(s.batch(1), s.batch(2))

    def test_sharding_partition(self):
        """Shards are disjoint rows of the same global batch."""
        full = TokenStream(500, 16, 8, seed=1, num_shards=1, shard=0).batch(5)
        s0 = TokenStream(500, 16, 8, seed=1, num_shards=2, shard=0).batch(5)
        s1 = TokenStream(500, 16, 8, seed=1, num_shards=2, shard=1).batch(5)
        assert s0.shape == (4, 16) and s1.shape == (4, 16)
        assert not np.array_equal(s0, s1)

    def test_in_vocab(self):
        t = TokenStream(100, 64, 4, seed=0).batch(0)
        assert t.min() >= 0 and t.max() < 100


class TestMonitor:
    def test_straggler_detection(self):
        mon = StragglerMonitor(threshold=1.5)
        for step in range(5):
            for h in range(4):
                mon.record(f"h{h}", 1.0 if h != 2 else 2.5, now=step * 10.0)
        assert mon.verdict("h2", now=50.0) == "straggler"
        assert mon.verdict("h0", now=50.0) == "ok"

    def test_stall_detection(self):
        mon = StragglerMonitor(stall_timeout_s=30)
        mon.record("h0", 1.0, now=0.0)
        assert mon.verdict("h0", now=10.0) == "ok"
        assert mon.verdict("h0", now=100.0) == "stall"


class TestCompression:
    def test_quant_dequant_error_feedback(self):
        from repro.distributed.compression import (_quant_dequant_int8,
                                                   compress_state_init)
        x = jax.random.normal(jax.random.PRNGKey(0), (100,))
        q, scale = _quant_dequant_int8(x)
        err = x - q.astype(jnp.float32) * scale
        # error bounded by half LSB
        assert float(jnp.max(jnp.abs(err))) <= float(scale) * 0.5 + 1e-6

    def test_error_feedback_converges(self):
        """Repeated compressed estimates of a CONSTANT gradient converge in
        average thanks to error feedback (the QSGD guarantee)."""
        from repro.distributed.compression import compressed_psum
        g = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 1e-3

        # single-device psum via vmap-free trick: axis over pmap of size 1
        def step(err):
            ghat, err = jax.vmap(
                lambda g, e: compressed_psum(g, e, "i"), axis_name="i")(
                    g[None], err[None])
            return ghat[0], err[0]

        err = jnp.zeros_like(g)
        est = jnp.zeros_like(g)
        n = 50
        for _ in range(n):
            ghat, err = step(err)
            est = est + ghat / n
        assert float(jnp.max(jnp.abs(est - g))) < 2e-4


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """End-to-end dry-run machinery on 8 virtual devices (mesh 4x2),
    including roofline extraction — the same code path as the 256/512-chip
    run, in miniature."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax
from repro.configs import get_smoke
from repro.distributed import sharding as sh
from repro.launch import specs as S
from repro.launch.dryrun import lower_cell, roofline
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg = dataclasses.replace(get_smoke("qwen3-8b"), remat=True)
cell = S.ShapeCell("t", 128, 8, "train")
with mesh:
    lowered = lower_cell(cfg, cell, mesh)
    comp = lowered.compile()
r = roofline(comp, comp.as_text(), 8, cfg, cell)
m = comp.memory_analysis()
assert r["hlo_flops_per_device"] > 0
assert r["collective_bytes"]["total"] > 0   # multi-pod must communicate
assert m.temp_size_in_bytes > 0
print("MINI_DRYRUN_OK", r["dominant"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert "MINI_DRYRUN_OK" in out.stdout, out.stdout + out.stderr
