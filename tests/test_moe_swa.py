"""Sparse experts with windowed and full attention layers (the Mellum 2
block) on the served path, against the benchmark's plain float32
reference (``bench/references/moe_swa_gqa.py``) at a small size:

  * chunked prefill through the paged engine's admission and extension
    pieces, then a paged one-token decode or a chain verify on the ref
    and the Pallas (interpreted) attention paths, give the reference's
    logits, and the served Medusa tree's tokens are its argmax;
  * the full layers' YaRN table is the closed form;
  * dropless experts give a per-token loop's result at any number of
    tokens in the call;
  * dense stacks are untouched: same logits as recorded from the parent
    program (``data/dense_logits.json``).

Tolerances: everything here runs in float32, so the program and the
reference differ only in summation order: 2e-4 on logits of magnitude
~1-3.  The int8 control of the same reference reads gaps of 3e-2 and
more on these weights (``test_int8_control_is_outside_the_tolerance``).
"""
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, Yarn
from repro.core.speculative import tree as T
from repro.kernels import ops
from repro.models import common as cm
from repro.models import mlp
from repro.models.api import get_model
from repro.runtime.engine import DecodeEngine, DecodeStrategy

REPO = Path(__file__).resolve().parents[1]
S, F = "sliding_attention", "full_attention"
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4, "layer_types": [S, S, S, F],
    "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "tie_word_embeddings": False, "attention_bias": False,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 4, "original_max_position_embeddings":
                           512, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}}
TOL = 2e-4
PAD, N_OUT = 128, 64


def _reference():
    sys.path.insert(0, str(REPO))
    path = REPO / "bench/references/moe_swa_gqa.py"
    spec = importlib.util.spec_from_file_location("ref_moe_swa_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="tiny-moe-swa", **REF.program_kwargs(CONFIG),
                      medusa_heads=2, medusa_top_k=2, dtype="float32")
    dims = dict(REF.dims(CONFIG), vocab_padded=cfg.padded_vocab,
                dtype="float32", n_out_max=N_OUT)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 16))
    params = REF.make_weights(dims, keys)
    model = get_model(cfg)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (52,), 0,
                                        cfg.vocab_size), np.int32)
    # reference logits at positions 0 .. 50 (each predicts the next token)
    ref = REF.logits(params, dims, seq[:1], seq[1:], pad_to=PAD)
    return cfg, dims, params, model, seq, ref


def _prefilled(model, params, seq, n_prompt, piece):
    """A paged bank of one row holding ``seq[:n_prompt]``, admitted as
    the scheduler does with chunked prefill: the first piece, then the
    rest a piece at a time."""
    eng = DecodeEngine(model, params, max_len=96, paged=True, page_size=4,
                       chunk=2)
    first = {"tokens": jnp.asarray(seq[None, :piece])}
    row = eng.sched_prefill(first)
    state = eng.sched_blank(row, 1)
    state, _ = eng.sched_admit(state, 0, first, n_tokens=8,
                               reserve_len=n_prompt)
    for s in range(piece, n_prompt, piece):
        toks = np.zeros((1, piece), np.int32)
        n = min(piece, n_prompt - s)
        toks[0, :n] = seq[s:s + n]
        state, _ = eng.sched_extend(state, 0, jnp.asarray(toks), n)
    return eng, state


@pytest.mark.parametrize("W", [1, 6], ids=["decode", "verify"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_chunked_prefill_then_paged_verify_matches_reference(setup, backend,
                                                             W):
    cfg, dims, params, model, seq, ref = setup
    n_prompt = 44
    eng, state = _prefilled(model, params, seq, n_prompt, piece=16)
    chain = T.Tree.from_spec(T.chain_spec(W))
    toks = jnp.asarray(seq[None, n_prompt:n_prompt + W])
    logits, extras = model.verify(params, state.cache, toks, chain,
                                  backend=backend)
    got = np.asarray(logits[0])
    want = ref[n_prompt:n_prompt + W]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert int(extras["moe"][0]) == 1
    assert int(extras["moe"][1]) == cfg.num_layers * W * \
        cfg.experts_per_token


def test_served_tokens_follow_the_reference(setup):
    """The whole served path: Medusa tree verify + commit over a paged
    bank after chunked prefill; every served token is the reference's
    argmax at its position."""
    cfg, dims, params, model, seq, ref = setup
    from repro.core.speculative.medusa import init_medusa
    from repro.runtime.scheduler import ContinuousScheduler, Request
    heads = init_medusa(cfg, jax.random.PRNGKey(3))
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        4)
    eng = DecodeEngine(model, params, heads=heads,
                       strategy=DecodeStrategy.medusa(spec), max_len=96,
                       paged=True, page_size=4, chunk=4)
    sched = ContinuousScheduler(eng, batch=1, chunk=4, prefill_chunk=16)
    prompt = seq[:40]
    res, stats = sched.serve([Request(req_id=0, tokens=prompt,
                                      n_tokens=10)])
    served = np.asarray(res[0].tokens)
    want = REF.logits(params, dims, prompt, served, pad_to=PAD)
    gap = want.max(-1) - want[np.arange(len(served)), served]
    assert gap.max() <= TOL, gap
    moe = stats["moe"]
    assert moe["layers"] == cfg.num_layers
    assert moe["prefill"]["steps"] == 3            # pieces of 16, 16, 8
    assert moe["decode"]["steps"] >= 1
    assert moe["decode"]["picks"] == moe["decode"]["steps"] * \
        cfg.num_layers * spec.width * cfg.experts_per_token
    touched = moe["decode"]["experts_touched"]
    assert moe["decode"]["steps"] * cfg.num_layers <= touched <= \
        moe["decode"]["steps"] * cfg.num_layers * cfg.num_experts


def test_int8_control_is_outside_the_tolerance(setup):
    cfg, dims, params, model, seq, ref = setup
    ctrl = REF.logits(params, dims, seq[:1], seq[1:], pad_to=PAD,
                      mode="int8")
    assert np.abs(ctrl - ref).max() > 100 * TOL


def test_window_binds(setup):
    """The window changes the answer at this context: the same stack with
    every layer full gives other logits past position 8."""
    cfg, dims, params, model, seq, ref = setup
    other = REF.logits(params, dict(dims, layer_types=(F,) * 4), seq[:1],
                       seq[1:], pad_to=PAD)
    assert np.abs(other[20:] - ref[20:]).max() > 100 * TOL


def test_yarn_frequencies_are_the_closed_form():
    y = Yarn(factor=16, original_max_position=8192, beta_fast=32,
             beta_slow=1, attention_factor=1.2772588722239782)
    d, b, s, L0 = 128, 5e5, 16.0, 8192
    got = cm.yarn_freqs(d, b, y)

    def corr(r):
        return d * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(b))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (18, 35)
    i = np.arange(d // 2)
    theta = b ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, theta / s * ramp + theta * (1 - ramp),
                               rtol=1e-12)
    ref_dims = {"head_dim": d, "yarn_theta": b, "yarn_factor": s,
                "yarn_original": L0, "yarn_beta_fast": 32.0,
                "yarn_beta_slow": 1.0}
    np.testing.assert_allclose(REF.yarn_inv_freq(ref_dims), got, rtol=1e-12)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("n_tokens", [1, 16, 128])
def test_dropless_experts_match_a_per_token_loop(impl, n_tokens,
                                                 monkeypatch):
    cfg = ModelConfig(name="e", arch_type="moe", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=64,
                      num_experts=8, experts_per_token=2, dtype="float32")
    p = mlp.moe_init(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n_tokens, 32))
    monkeypatch.setattr(ops, "_expert_matmul_impl", lambda: impl)
    stacks = {k: p[k][None] for k in mlp.EXPERT_WEIGHTS}     # one layer
    out, counts = mlp.moe_dropless(cfg, dict(p, layer=0), x, stacks)
    _, gates, experts = mlp.route(cfg, p, x[0])
    want = np.zeros((n_tokens, 32))
    for t in range(n_tokens):
        for k in range(cfg.experts_per_token):
            e = int(experts[t, k])
            h = jax.nn.silu(x[0, t] @ p["w_gate"][e]) * (x[0, t] @ p["w_up"][e])
            want[t] += float(gates[t, k]) * np.asarray(h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=1e-5)
    assert int(counts[0]) == n_tokens * cfg.experts_per_token
    assert int(counts[1]) == len(np.unique(np.asarray(experts)))


def _dense_logits(arch):
    from repro.configs import get_config
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                              cfg.vocab_size)
    logits, _, cache = model.prefill(params, {"tokens": toks[:, :20]},
                                     max_len=32)
    chain = T.Tree.from_spec(T.chain_spec(4))
    vlog, _ = model.verify(params, cache, toks[:, 20:24], chain)
    return np.concatenate([np.asarray(logits[0, -1:]),
                           np.asarray(vlog[0])]).astype(np.float64)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "vicuna-7b"])
def test_dense_logits_unchanged(arch):
    """Dense stacks run the code they ran before layer kinds existed:
    their prefill and verify logits are the parent program's, as recorded
    (exactly rounded sums, so the same bits give the same numbers)."""
    want = json.loads((REPO / "tests/data/dense_logits.json").read_text())
    x = _dense_logits(arch)
    got = [math.fsum(x.ravel().tolist()),
           math.fsum((x * x).ravel().tolist())]
    assert got == want[arch]
