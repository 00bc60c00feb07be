"""The plain reference against the program at smoke size on the CPU:
loss, gradients and one Adam update on one device (float32 on both
sides, so they agree to rounding)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as ct
import harness
import reference
import tokens


@pytest.mark.parametrize("kind", ["olmo", "qwen"])
def test_loss_grads_and_adam_match_program(tmp_path, kind):
    from repro.models import Model
    from repro.parallel.sharding import Runtime
    from repro.train import loss as loss_lib
    from repro.train import optimizer as opt_lib

    cell = harness.load_cell(ct.make_root(tmp_path, kind, dtype="float32"),
                             "tiny.tiny")
    cfg, opt = cell.config, cell.traffic["optimizer"]
    params = jax.jit(lambda k: reference.init_params(cfg, k))(
        reference.seed_key(7))
    b = tokens.batch(7, 0, cell.rows, cell.traffic["seq_len"],
                     cfg["vocab_size"], 1.3)
    model = Model(harness.model_config(cfg), Runtime())

    def loss_fn(p):
        logits, _ = model.apply_train(p, b["tokens"])
        return loss_lib.sharded_xent(logits, b["labels"], model.rt,
                                     cfg["vocab_size"])[0]

    l_prog, g_prog = jax.jit(jax.value_and_grad(loss_fn))(params)
    ref = reference.Reference(cfg, opt, jax.devices()[:1])
    l_ref, g_ref = ref._grads(params, jnp.asarray(b["tokens"]),
                              jnp.asarray(b["labels"]))
    assert float(l_prog) == pytest.approx(float(l_ref), rel=1e-5)
    assert jax.tree.structure(g_prog) == jax.tree.structure(g_ref)
    for a, c in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        scale = float(jnp.max(jnp.abs(c)))
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5 * scale)

    gnorm = math.sqrt(sum(float(jnp.sum(g * g))
                          for g in jax.tree.leaves(g_prog)))
    clip = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    p_prog, st = opt_lib.adam_update(g_prog, opt_lib.adam_init(params),
                                     params, opt_lib.OptConfig(**opt), clip)
    zeros = jax.tree.map(jnp.zeros_like, params)
    p_ref, mu, _, c_ref = reference.adamw(params, g_ref, zeros, zeros, 0, opt)
    assert float(c_ref) == pytest.approx(clip, rel=1e-5)
    for a, c in zip(jax.tree.leaves(st.mu), jax.tree.leaves(mu)):
        np.testing.assert_allclose(a, c, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(c))))
    for a, c, p in zip(jax.tree.leaves(p_prog), jax.tree.leaves(p_ref),
                       jax.tree.leaves(params)):
        # the update itself (lr 3e-6 on the first warm-up step) agrees
        np.testing.assert_allclose(a - p, c - p, rtol=1e-3, atol=2e-8)
        assert float(jnp.max(jnp.abs(a - p))) > 0


def test_reference_blocks_agree_with_one_block(tmp_path, monkeypatch):
    """Blocked attention and loss give what one block gives."""
    cell = harness.load_cell(
        ct.make_root(tmp_path, "qwen", dtype="float32", seq=128), "tiny.tiny")
    cfg, opt = cell.config, cell.traffic["optimizer"]
    b = tokens.batch(3, 0, cell.rows, 128, cfg["vocab_size"], 1.3)
    out = {}
    for blk in (32, 128):
        monkeypatch.setattr(reference, "Q_BLOCK", blk)
        monkeypatch.setattr(reference, "T_BLOCK", blk)
        out[blk] = reference.Reference(cfg, opt, jax.devices()[:1]).run(3, [b])
    assert out[32]["losses"] == pytest.approx(out[128]["losses"], rel=1e-6)
    for k, v in out[128]["grad_norms"].items():
        assert out[32]["grad_norms"][k] == pytest.approx(v, rel=1e-5)
