"""End-to-end training integration: loss decreases, checkpoints resume
bit-exact, gradient compression still converges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data.lm_data import DataConfig, batch_at_step
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import checkpoint as ckpt
from repro.train.loop import make_train_step


@pytest.fixture(scope="module")
def tiny_cfg():
    return get_arch("tinyllama-1.1b").scaled(
        name="tiny-test", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=256)


def _run(cfg, steps, *, compress=False, params=None, opt_state=None,
         start=0, seed=0):
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        step_fn, *_, init_opt = make_train_step(
            cfg, mesh, lr=5e-3, total_steps=steps, donate=False,
            compress_pod_grads=compress)
        if params is None:
            params = T.init_params(cfg, jax.random.key(seed), jnp.float32)
            opt_state = init_opt(params)
        dcfg = DataConfig(cfg.vocab_size, 64, 4, seed=seed)
        losses = []
        for s in range(start, steps):
            b = batch_at_step(dcfg, s)
            params, opt_state, m = step_fn(
                params, opt_state,
                {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])})
            losses.append(float(m["loss"]))
        return params, opt_state, losses


def test_loss_decreases(tiny_cfg):
    _, _, losses = _run(tiny_cfg, 30)
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_compressed_grads_still_converge(tiny_cfg):
    _, _, losses = _run(tiny_cfg, 30, compress=True)
    assert losses[-1] < losses[0] - 0.4, (losses[0], losses[-1])


def test_checkpoint_resume_bit_exact(tiny_cfg, tmp_path):
    """Crash at step 10, resume: steps 10..20 must equal the uninterrupted
    run (deterministic data + saved optimizer state)."""
    p1, o1, l_full = _run(tiny_cfg, 20)

    p2, o2, _ = _run(tiny_cfg, 10)
    ckpt.save(10, (p2, o2), tmp_path)
    (p3, o3), step = ckpt.restore((p2, o2), tmp_path)
    assert step == 10
    p4, o4, l_resumed = _run(tiny_cfg, 20, params=p3, opt_state=o3, start=10)

    np.testing.assert_allclose(l_resumed, l_full[10:], rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), p1, p4)
