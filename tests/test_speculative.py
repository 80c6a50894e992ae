"""Speculative decoding invariants: LOSSLESSNESS (greedy spec == greedy
sequential) per family, accept-walk properties, emitted-token accounting,
and the draft's blocked top-k (the same indices and values as
``lax.top_k``, without a sort of the whole vocabulary)."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _mini_hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.speculative import tree as T
from repro.core.speculative.medusa import (blocked_top_k, draft_candidates,
                                           init_medusa, medusa_logits)
from repro.core.speculative.verify import accept_walk, spec_prefill, spec_step
from repro.models.api import get_model
from repro.runtime.engine import SpeculativeEngine
from repro.runtime.scheduler import ContinuousScheduler, Request


def _greedy_reference(model, params, toks, n):
    logits, _, cache = model.prefill(params, {"tokens": toks}, max_len=128)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    dec = jax.jit(lambda p, c, t: model.decode(p, c, t))
    out = [int(cur[0])]
    for _ in range(n - 1):
        lg, cache = dec(params, cache, cur[:, None])
        cur = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
        out.append(int(cur[0]))
    return out


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-7b", "xlstm-125m"])
def test_speculative_lossless(arch):
    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    N = 16
    ref = _greedy_reference(model, params, toks, N)

    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k), 8)
    tr = T.Tree.from_spec(spec)
    st_ = spec_prefill(model, params, heads, {"tokens": toks}, max_len=128)
    out = [int(st_.cur_token[0])]
    step = jax.jit(lambda p, h, s: spec_step(model, p, h, tr, s))
    while len(out) < N:
        st_, emitted, n = step(params, heads, st_)
        out.extend(int(t) for t in np.asarray(emitted[0])[:int(n[0])])
    assert out[:N] == ref, f"{arch}: speculative != sequential greedy"


# ---------------------------------------------------------------------------
# accept_walk vs a trusted numpy reference, on random trees/logits
# ---------------------------------------------------------------------------
def _np_accept(parent, depth, tree_tokens, targets):
    cur, n = 0, 1
    while True:
        nxt = None
        for i in range(len(parent)):
            if parent[i] == cur and tree_tokens[i] == targets[cur] \
                    and depth[i] == depth[cur] + 1:
                nxt = i
                break
        if nxt is None:
            return n, cur
        cur, n = nxt, n + 1


@given(seed=st.integers(0, 10_000), width=st.sampled_from([2, 4, 8, 16]))
@settings(max_examples=40, deadline=None)
def test_accept_walk_matches_numpy(seed, width):
    rng = np.random.default_rng(seed)
    nodes = [(-1, 0, 0)]
    used = set()
    while len(nodes) < width:
        p = int(rng.integers(0, len(nodes)))
        r = int(rng.integers(0, 6))
        if (p, r) in used or nodes[p][1] >= 4:
            continue
        used.add((p, r))
        nodes.append((p, nodes[p][1] + 1, r))
    spec = T.spec_from_nodes(nodes)
    tr = T.Tree.from_spec(spec)
    W = spec.width
    V = 12                                         # small vocab => collisions
    tree_tokens = rng.integers(0, V, (1, W)).astype(np.int32)
    logits = rng.normal(size=(1, W, V)).astype(np.float32)
    targets = logits[0].argmax(-1)

    acc = accept_walk(tr, jnp.asarray(tree_tokens), jnp.asarray(logits))
    n_ref, last_ref = _np_accept(spec.parent, spec.depth, tree_tokens[0],
                                 targets)
    assert int(acc["n_accept"][0]) == n_ref
    assert int(acc["bonus"][0]) == targets[int(acc["last_node"][0])]
    # chain is a valid root->last path
    chain = np.asarray(acc["chain"][0])
    assert chain[0] == 0
    n = int(acc["n_accept"][0])
    for j in range(1, n):
        assert spec.parent[chain[j]] == chain[j - 1]


# ---------------------------------------------------------------------------
# the draft's top-k: blocked_top_k == lax.top_k, indices and values bit for
# bit, ties to the lower index
# ---------------------------------------------------------------------------
def _probs(shape, rows, seed):
    """Softmax rows: ``random`` logits, or logits rounded to thirds, where
    the top k span a few tied levels across blocks (``tied``) or all tie
    at the top level, a sixth of the row (``flat``)."""
    rng = np.random.default_rng(seed)
    logits = {"random": lambda: rng.normal(0, 3, shape),
              "tied": lambda: np.round(rng.normal(0, 1, shape) * 3) / 3,
              "flat": lambda: np.round(rng.uniform(0, 1, shape) * 3) / 3,
              }[rows]()
    return jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)


def _assert_same_top_k(got, want):
    (gv, gi), (wv, wi) = got, want
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gv).view(np.uint32),
                                  np.asarray(wv).view(np.uint32))


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("rows", ["random", "tied", "flat"])
@pytest.mark.parametrize("vocab", [151_936, 32_000, 50_257, 1_000])
def test_blocked_top_k_equals_lax_top_k(vocab, rows, batch):
    k = 10
    probs = _probs((batch, 4, vocab), rows, seed=vocab + batch)
    got = jax.jit(blocked_top_k, static_argnums=1)(probs, k)
    _assert_same_top_k(got, jax.lax.top_k(probs, k))


def _draft_cfg(vocab):
    """The smoke config with ``vocab`` columns: 512 are 4 blocks, as many as
    the 4 candidates a head keeps, so every block is a candidate; 2,000 are
    16 blocks, the last one short, of which the 4 best are."""
    return dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                               vocab_size=vocab)


@pytest.mark.parametrize("vocab", [512, 2_000])
def test_draft_candidates_equals_lax_top_k(vocab):
    cfg = _draft_cfg(vocab)
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    hidden = jax.random.normal(jax.random.PRNGKey(2), (3, cfg.d_model))
    # op by op, so that the reference's probabilities are the same bits
    idx, vals = draft_candidates(cfg, heads, hidden, cfg.medusa_top_k)
    probs = jax.nn.softmax(
        medusa_logits(cfg, heads, hidden).astype(jnp.float32), axis=-1)
    want_v, want_i = jax.lax.top_k(probs, cfg.medusa_top_k)
    assert idx.dtype == jnp.int32
    _assert_same_top_k((vals, idx), (want_v, want_i))


def _sorted_widths(text):
    """Last-axis width of every top-k and sort operand in StableHLO text."""
    dims = re.findall(r"chlo\.top_k\(.*?: tensor<([\dx]+)x\w+>", text)
    dims += re.findall(r'"stablehlo\.sort".*?\}\) : \(tensor<([\dx]+)x\w+>',
                       text, re.S)
    return sorted({int(d.split("x")[-1]) for d in dims})


def test_draft_lowers_without_a_full_vocabulary_sort():
    cfg = get_config("qwen2-0.5b")                    # 151,936 columns
    heads = jax.eval_shape(lambda: init_medusa(cfg, jax.random.PRNGKey(0)))
    hidden = jax.ShapeDtypeStruct((1, cfg.d_model), jnp.dtype(cfg.dtype))
    text = jax.jit(functools.partial(
        draft_candidates, cfg, top_k=cfg.medusa_top_k)).lower(
            heads, hidden).as_text(debug_info=True)
    # top-k of the 1,187 block maxima, sort of the 10 chosen block ids,
    # top-k of their 1,280 candidates
    assert _sorted_widths(text) == [10, 1_187, 1_280]
    assert "draft_topk" in text
    whole = jax.jit(lambda x: jax.lax.top_k(x, 10)).lower(
        jax.ShapeDtypeStruct((1, 4, cfg.vocab_size), jnp.float32)).as_text()
    assert _sorted_widths(whole) == [cfg.vocab_size]


def test_scheduler_serves_a_draft_of_more_blocks_than_k():
    """The served path drafts from 4 of 16 blocks and still emits the
    engine's own greedy tokens."""
    cfg = _draft_cfg(2_000)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    heads = init_medusa(cfg, jax.random.PRNGKey(7))
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k), 8)
    eng = SpeculativeEngine(model, heads, params, spec, max_len=64, chunk=4)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (8,), 0,
                                         cfg.vocab_size), np.int32)
    results, _ = ContinuousScheduler(eng, batch=1).serve(
        [Request(req_id=0, tokens=toks, n_tokens=6)])
    solo, _ = eng.generate({"tokens": toks[None]}, 6)
    np.testing.assert_array_equal(results[0].tokens,
                                  np.atleast_2d(solo)[0][:6])
