"""Speculative decoding invariants: LOSSLESSNESS (greedy spec == greedy
sequential) per family, accept-walk properties, emitted-token accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _mini_hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.speculative import tree as T
from repro.core.speculative.medusa import init_medusa
from repro.core.speculative.verify import accept_walk, spec_prefill, spec_step
from repro.models.api import get_model


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
