"""Medusa drafting heads (paper's default speculative approach, §III-A).

Each head h predicts the token at offset h+1 from the current hidden state:
  head_h(x) = (x + silu(x @ W_h)) @ O_h        (ResBlock + linear)

Heads are separate from base-model params (they're trained post-hoc; the
end-to-end example trains them with the base model frozen).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm


def init_medusa(cfg, rng):
    ks = jax.random.split(rng, cfg.medusa_heads)
    dt = jnp.dtype(cfg.dtype)

    def head_init(k):
        k1, k2 = jax.random.split(k)
        return {
            "w": cm.dense_init(k1, cfg.d_model, cfg.d_model, dt, scale=0.02),
            "out": cm.dense_init(k2, cfg.d_model, cfg.padded_vocab, dt),
        }

    return cm.stack_init(rng, cfg.medusa_heads, head_init)


def medusa_logits(cfg, heads, hidden):
    """hidden: (..., d) -> (..., H, V) — vmapped over stacked heads."""
    def one(hp):
        h = hidden + jax.nn.silu(hidden @ hp["w"])
        return h @ hp["out"]

    out = jax.vmap(one)(heads)                     # (H, ..., Vp)
    return jnp.moveaxis(out, 0, -2)[..., :cfg.vocab_size]


LANES = 128        # TPU vector lane width: the block of the two-stage top-k


def blocked_top_k(x, k):
    """``jax.lax.top_k(x, k)`` over the last axis, bit for bit, without
    sorting the whole row (on a TPU a wide top-k lowers to a full sort).

    The k largest values lie in the k blocks with the largest maxima, with
    ties to the lower index as ``lax.top_k`` breaks them: rank the maxima
    of the 128-lane blocks, gather the k best (every block, where there are
    no more) in vocabulary order, and take the top k of those candidates.
    Exact for inputs without -0.0 (``max`` does not tell it from 0.0),
    such as probabilities."""
    n = x.shape[-1]
    nb = -(-n // LANES)
    kb = min(k, nb)
    pad = nb * LANES - n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                    constant_values=-jnp.inf)
    xb = x.reshape(x.shape[:-1] + (nb, LANES))
    _, blk = jax.lax.top_k(xb.max(axis=-1), kb)
    blk = jnp.sort(blk, axis=-1)                      # vocabulary order
    cand = jnp.take_along_axis(xb, blk[..., None], axis=-2)
    vals, pos = jax.lax.top_k(cand.reshape(x.shape[:-1] + (kb * LANES,)), k)
    idx = jnp.take_along_axis(blk, pos // LANES, axis=-1) * LANES \
        + pos % LANES
    return vals, idx


def draft_candidates(cfg, heads, hidden, top_k):
    """hidden: (B, d) -> candidate tokens (B, H, K) + probs (B, H, K)."""
    logits = medusa_logits(cfg, heads, hidden)     # (B, H, V)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    with jax.named_scope("draft_topk"):
        vals, idx = blocked_top_k(probs, top_k)
    return idx.astype(jnp.int32), vals


def head_accuracies(cfg, model, params, heads, token_batches):
    """REAL per-head top-k accuracy table (replaces the fitted calibration
    table): accs[h, k] = P(head h's rank-k candidate is the target), the
    quantity ARCA's tree construction and expected-acceptance estimator
    consume.  ``token_batches``: iterable of (B, S) int32 token arrays
    (calibration prompts).  Used by the end-to-end example and the
    trained-heads arm of ``benchmarks/engine_bench.py``."""
    import numpy as np

    H, K = cfg.medusa_heads, cfg.medusa_top_k
    hits = np.zeros((H, K))
    counts = 0
    for toks in token_batches:
        toks = jnp.asarray(np.asarray(toks, np.int32))
        seq = int(toks.shape[1])
        _, extras, _ = model.prefill(params, {"tokens": toks},
                                     return_cache=False)
        logits = medusa_logits(cfg, heads, extras["hidden"])  # (B,S,H,V)
        _, top = jax.lax.top_k(logits, K)                     # (B,S,H,K)
        top = np.asarray(top)
        tk = np.asarray(toks)
        for h in range(H):
            off = h + 2       # hidden at t drives head h toward token t+h+2
            if off >= seq:
                continue
            tgt = tk[:, off:]                                 # (B, S-off)
            pred = top[:, :seq - off, h]                      # (B, S-off, K)
            for k in range(K):
                hits[h, k] += float(np.mean(pred[..., k] == tgt))
        counts += 1
    return hits / max(counts, 1)


def expand_tree_tokens(tree, cur_token, candidates):
    """Fill tree slots: node 0 = cur committed token; node n (depth d>0) =
    head (d-1)'s rank[n] candidate.

    cur_token: (B,), candidates: (B, H, K) -> (B, W) int32.
    """
    B = cur_token.shape[0]
    head_idx = jnp.maximum(tree.depth - 1, 0)          # (W,)
    cand = candidates[:, head_idx, tree.rank]          # (B, W)
    root = jnp.zeros_like(tree.depth) == tree.depth    # depth==0 mask
    return jnp.where(root[None, :], cur_token[:, None], cand)
