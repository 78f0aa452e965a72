"""Explicit-collective MoE (shard_map EP schedule) vs the dense oracle.

The multi-device check runs in a subprocess so the 8 virtual host devices
don't leak into the rest of the suite (jax locks device count at init).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.config import ModelConfig


def _cfg(**kw):
    base = dict(name="t", family="moe", n_layers=2, d_model=32, vocab=64,
                n_heads=2, n_kv_heads=2, d_ff=64, n_experts=8, top_k=2,
                moe_d_ff=64, dtype="float32", capacity_factor=8.0)
    base.update(kw)
    return ModelConfig(**base)


def test_a2a_unavailable_without_mesh_falls_back():
    cfg = _cfg(moe_dispatch="a2a")
    p = L.moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)),
                    jnp.float32) * 0.1
    out, aux = L.moe(p, cfg, x)          # no mesh -> sorted/dense fallback
    want, aux_w = L.moe_dense(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.models import layers as L
    from repro.models.config import ModelConfig
    from repro.parallel.sharding import RULE_VARIANTS, use_mesh

    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=32,
                      vocab=64, n_heads=2, n_kv_heads=2, d_ff=64,
                      n_experts=8, top_k=2, moe_d_ff=64, dtype="float32",
                      capacity_factor=8.0, moe_dispatch="a2a")
    p = L.moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16, 32)),
                    jnp.float32) * 0.1
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    rules = RULE_VARIANTS["moe_a2a"]
    want, _ = L.moe_dense(p, cfg, x)
    with use_mesh(mesh, rules):
        got, _ = jax.jit(lambda p, x: L.moe(p, cfg, x))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)

    def loss_a2a(p, x):
        with use_mesh(mesh, rules):
            y, _ = L.moe(p, cfg, x)
        return jnp.sum(y ** 2)

    def loss_dense(p, x):
        y, _ = L.moe_dense(p, cfg, x)
        return jnp.sum(y ** 2)

    with use_mesh(mesh, rules):
        g1 = jax.jit(jax.grad(loss_a2a))(p, x)
    g2 = jax.grad(loss_dense)(p, x)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=5e-4, rtol=5e-3, err_msg=k)
    print("A2A_OK")
""")


@pytest.mark.slow
def test_a2a_matches_oracle_on_8_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices, never the chip
    out = subprocess.run([sys.executable, "-c", MULTIDEV], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "A2A_OK" in out.stdout
