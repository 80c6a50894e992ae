"""Training substrate: loss decreases, Medusa heads learn, checkpoint I/O."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import MarkovDataset
from repro.models.api import get_model
from repro.training import checkpoint
from repro.training.optimizer import adamw_init
from repro.training.train import medusa_step, train_step
from repro.core.speculative.medusa import init_medusa


def test_loss_decreases():
    cfg = get_config("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    data = MarkovDataset(cfg.vocab_size, seed=1)
    step = jax.jit(lambda p, o, b: train_step(cfg, model, p, o, b, lr=3e-3))
    losses = []
    for batch in data.batches(8, 64, 30):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8, losses[:3] + losses[-3:]


def test_medusa_heads_learn():
    cfg = get_config("qwen2-0.5b").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    heads = init_medusa(cfg, jax.random.PRNGKey(1))
    hopt = adamw_init(heads)
    data = MarkovDataset(cfg.vocab_size, seed=1)
    step = jax.jit(lambda h, o, b: medusa_step(cfg, model, params, h, o, b,
                                              lr=3e-3))
    losses = []
    # 25 steps lands right at the 0.9 threshold (measured ratio 0.915 —
    # flaky); 60 steps gives a comfortable margin (~0.887)
    for batch in data.batches(8, 64, 60):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        heads, hopt, m = step(heads, hopt, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("xlstm-125m").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    path = os.path.join(tmp_path, "ck.npz")
    checkpoint.save(path, params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    restored = checkpoint.restore(path, zeros)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_aux_loss_nonzero():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    # the load-balance loss is the training forward's (serving prefill
    # routes dropless and computes no loss)
    _, extras, _ = model.prefill(params, {"tokens": toks},
                                 return_cache=False)
    assert float(extras["aux_loss"]) > 0.0
