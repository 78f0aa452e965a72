"""Elastic scaling: a checkpoint taken on one mesh restores onto a DIFFERENT
mesh and training continues bit-compatibly.

Checkpoints store *global* host arrays (save_pytree snapshots via
np.asarray), so restoring is just device_put with the new mesh's shardings --
this test proves it end to end on 8 virtual devices: train on a (2,4) mesh,
checkpoint, restore onto a (4,2) mesh (as after losing/gaining nodes), train
one more step, and match the uninterrupted run's loss exactly.
"""

import os
import subprocess

import pytest
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.parallel.sharding import DEFAULT_RULES, tree_shardings, use_mesh
    from repro.train import (AdamWConfig, AsyncCheckpointer, SyntheticCorpus,
                             DataConfig, init_state, make_train_step,
                             restore_latest, state_specs)

    cfg = get_config("tinyllama-1.1b", reduced=True)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=3)
    corpus = SyntheticCorpus(dcfg)
    step_raw = make_train_step(cfg, ocfg)

    def mesh_of(shape):
        return Mesh(np.array(jax.devices()).reshape(shape), ("data", "model"))

    def run_steps(mesh, state, steps, start):
        with use_mesh(mesh, DEFAULT_RULES):
            jstep = jax.jit(lambda s, b: step_raw(s, b))
            losses = []
            for i in range(start, start + steps):
                batch = {k: jnp.asarray(v) for k, v in corpus.batch(i).items()}
                state, m = jstep(state, batch)
                losses.append(float(m["loss"]))
        return state, losses

    # uninterrupted reference on mesh A
    mesh_a = mesh_of((2, 4))
    with use_mesh(mesh_a, DEFAULT_RULES):
        sh_a = tree_shardings(mesh_a, state_specs(cfg), DEFAULT_RULES)
        s0 = jax.jit(lambda k: init_state(k, cfg, ocfg),
                     out_shardings=sh_a)(jax.random.PRNGKey(0))
    ref, ref_losses = run_steps(mesh_a, s0, 3, 0)

    # interrupted: 2 steps on mesh A, checkpoint, restore on mesh B (4,2)
    with tempfile.TemporaryDirectory() as d:
        part, l01 = run_steps(mesh_a, s0, 2, 0)
        ck = AsyncCheckpointer(d, keep=1)
        ck.save(2, part, block=True)
        del part

        mesh_b = mesh_of((4, 2))         # "the cluster changed shape"
        host_like = jax.tree.map(np.asarray, s0)
        step_no, host_state = restore_latest(d, host_like)
        assert step_no == 2
        sh_b = tree_shardings(mesh_b, state_specs(cfg), DEFAULT_RULES)
        state_b = jax.tree.map(
            lambda h, s: jax.device_put(np.asarray(h), s),
            host_state, sh_b)
        # NamedTuple reconstruction (tree.map preserves structure)
        _, l2 = run_steps(mesh_b, state_b, 1, 2)

    np.testing.assert_allclose(l01 + l2, ref_losses, rtol=1e-5)
    print("ELASTIC_OK", l01 + l2)
""")


@pytest.mark.slow
def test_checkpoint_restores_across_mesh_shapes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices, never the chip
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ELASTIC_OK" in out.stdout
