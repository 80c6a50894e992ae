"""GQA attention block: prefill / decode / tree-verify paths.

The tree-verify path is the heart of the Ghidorah reproduction: the W
speculative tokens attend to (a) the long KV cache — the *dense* part — and
(b) the W fresh tree KVs under the ancestor mask — the *sparse* part.  The two
parts are computed as separate online-softmax partials and merged (paper
§III-B2, Eq. 1).  On the real mesh the dense part is additionally sequence-
sharded across the `model` axis (core/hcmp/attention.py) and the same merge
combines the shards; the Pallas kernels in kernels/ implement the same math
with VMEM tiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.runtime.cache import batched_decode_mask, in_window, prefill_mask


def attn_init(cfg, rng):
    ks = jax.random.split(rng, 8)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": cm.dense_init(ks[0], d, cfg.num_heads * hd, _dt(cfg)),
        "wk": cm.dense_init(ks[1], d, cfg.num_kv_heads * hd, _dt(cfg)),
        "wv": cm.dense_init(ks[2], d, cfg.num_kv_heads * hd, _dt(cfg)),
        "wo": cm.dense_init(ks[3], cfg.num_heads * hd, d, _dt(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), _dt(cfg))
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), _dt(cfg))
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), _dt(cfg))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), _dt(cfg))
        p["k_norm"] = jnp.ones((hd,), _dt(cfg))
    return p


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _qkv(cfg, p, x, positions, rope=None):
    """x: (B, S, d) -> roped q (B,S,Hq,hd), k (B,S,Hkv,hd), v (B,S,Hkv,hd).
    ``rope``: the layer's rotary row (``common.rope_tables``), else the
    plain table at ``cfg.rope_theta``."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = cm.rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = cm.rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    q = cm.apply_rope(q, positions, cfg.rope_theta, rope)
    k = cm.apply_rope(k, positions, cfg.rope_theta, rope)
    return q, k, v


BLOCKED_PREFILL_THRESHOLD = 4096      # S above which prefill uses tiling
PREFILL_BLOCK = 1024


def attn_prefill(cfg, p, x, *, start_pos=0, window=0, causal=True,
                 rope=None):
    """Full-sequence (optionally causal/windowed) attention.  Returns
    (out, (k, v)) — k/v are the rope'd cache entries for [start, start+S).
    ``window`` may be a traced per-layer scalar and ``rope`` the layer's
    rotary row (a stack with ``layer_types``).

    Long sequences use the blocked online-softmax path (§Perf hillclimb A2):
    the naive form materializes (B, H, S, S) scores — at 32k prefill that
    single tensor dominates HBM traffic and the TP collectives."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :] + start_pos
    q, k, v = _qkv(cfg, p, x, positions, rope)
    if causal and S >= BLOCKED_PREFILL_THRESHOLD and \
            S % PREFILL_BLOCK == 0:
        o = _blocked_causal_attend(q, k, v, cfg.head_dim ** -0.5,
                                   window=window, block=PREFILL_BLOCK)
    else:
        if causal:
            mask = prefill_mask(S, window)[None, None]
        else:                                      # bidirectional (encoder)
            mask = jnp.ones((1, 1, S, S), bool)
        o = cm.gqa_attend(q, k, v, mask, cfg.head_dim ** -0.5)
    out = o.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


def _blocked_causal_attend(q, k, v, scale, *, window=0, block=1024):
    """Tiled causal attention with an online-softmax carry — (Cq, Ck) score
    tiles instead of the (S, S) matrix.  Masked (above-diagonal) tiles are
    still computed (2x FLOP waste vs a triangular schedule — a candidate A3
    iteration); memory/collective footprint is what this targets."""
    B, S, Hq, hd = q.shape
    nq = S // block
    qs = jnp.swapaxes(q.reshape(B, nq, block, Hq, hd), 0, 1)   # (nq,B,C,H,hd)
    ks = jnp.swapaxes(k.reshape(B, nq, block, k.shape[2], hd), 0, 1)
    vs = jnp.swapaxes(v.reshape(B, nq, block, v.shape[2], hd), 0, 1)
    base = jnp.arange(block)

    def q_step(_, qi_blk):
        i, qi = qi_blk
        qpos = i * block + base

        def kv_step(carry, kv_blk):
            j, kj, vj = kv_blk
            kpos = j * block + base
            ok = in_window(kpos[None, :] <= qpos[:, None], kpos[None, :],
                           qpos[:, None], window)
            o, m, l = cm.gqa_attend_partial(qi, kj, vj, ok[None, None], scale)
            return (cm.merge_partials_carry(carry, (o, m, l))), None

        init = (jnp.zeros((B, block, Hq, hd), jnp.float32),
                jnp.full((B, Hq, block), cm.NEG_INF, jnp.float32),
                jnp.zeros((B, Hq, block), jnp.float32))
        (o, m, l), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nq), ks, vs))
        l = jnp.maximum(l, 1e-30)
        out = o * (1.0 / jnp.transpose(l, (0, 2, 1)))[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    return jnp.swapaxes(outs, 0, 1).reshape(B, S, Hq, hd)


def attn_cross(cfg, p, x, enc_k, enc_v, *, pos=None, tree_depth=None):
    """Encoder-decoder cross-attention: queries over fixed encoder memory.

    enc_k/enc_v: (B, Senc, Hkv, hd) — precomputed, un-rope'd (absolute
    encoder positions are baked in at encode time via rope on k).
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    # cross-attn queries are not rotary-shifted against encoder memory
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(cfg.num_heads, hd)
    if cfg.qk_norm:
        q = cm.rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
    mask = jnp.ones((1, 1, S, enc_k.shape[1]), bool)
    o = cm.gqa_attend(q, enc_k, enc_v, mask, hd ** -0.5)
    return o.reshape(B, S, -1) @ p["wo"]


def cross_kv_init(cfg, p, enc_out):
    """Precompute the cross-attention K/V memory from encoder outputs."""
    B, S, _ = enc_out.shape
    hd = cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (enc_out @ p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].reshape(cfg.num_kv_heads, hd)
        v = v + p["bv"].reshape(cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        k = cm.rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    return k, v


def attn_verify(cfg, p, x, *, ck, cv, key_pos, pos, tree_depth, tree_mask,
                window=0, backend="ref", block_table=None,
                scale_k=None, scale_v=None, tree_kernel="dense", rope=None):
    """Tree-verification attention over W draft tokens (decode = W=1 case).

    x: (B, W, d); tree_depth: (W,) node depth (0 = first new token);
    tree_mask: (W, W) ancestor-or-self mask.
    ``pos`` and ``key_pos`` are per-sequence — () or (B,), and (S,) or (B, S)
    — because batched speculative decoding leaves each sequence at its own
    absolute position after a commit.

    Cache layout: dense (``block_table=None``) reads ck/cv as per-sequence
    rows (B, S, Hkv, hd); paged passes ONE layer's shared page pool
    ``(n_pages + 1, ps, Hkv, hd)`` plus ``block_table (B, max_pages)`` —
    the ref path gathers the logical view through the table, the Pallas
    path walks the table inside the kernel (scalar prefetch).  A quantized
    pool also passes ``scale_k/scale_v (n_pages + 1, Hkv)``; dequant is
    fused into the kernel's page walk (ref: ``gather_pages_dequant``).

    ``tree_kernel="sparse"`` (paged + pallas only) splits the verify into
    the cache-only page walk plus the block-masked W×W sparse tree kernel,
    merged by the Eq.-1 online-softmax rule; other layouts/backends fall
    back to their fused path (the split exists for the paged walk).  The
    mask math is layout-agnostic: ``key_pos`` is already logical.
    ``window`` masks keys at or below ``q_pos - window`` on every path (a
    Python int, or the layer's traced window in a stack with
    ``layer_types``): cached keys by position, fresh tree keys by depth
    (a chunked-prefill piece is a chain longer than a small window).
    Returns (out (B, W, d), (k_new, v_new)) — fresh KVs NOT yet committed.
    """
    B, W, _ = x.shape
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos_b[:, None] + tree_depth[None, :]           # (B, W)
    q, k_new, v_new = _qkv(cfg, p, x, positions, rope)
    tree_mask = in_window(tree_mask, tree_depth[None, :],
                          tree_depth[:, None], window)
    scale = cfg.head_dim ** -0.5

    if block_table is not None and backend == "pallas":
        from repro.kernels import ops as kops
        if tree_kernel == "sparse":
            cache_part = kops.paged_cache_attention(
                q, ck, cv, block_table, key_pos, pos_b, tree_depth,
                scale_k=scale_k, scale_v=scale_v, window=window)
            tree_part = kops.sparse_tree_attention_partial(
                q, k_new, v_new, tree_mask)
            o = cm.merge_partials([cache_part, tree_part]).astype(x.dtype)
        else:
            o = kops.paged_tree_attention(
                q, ck, cv, k_new, v_new, block_table, key_pos, pos_b,
                tree_depth, tree_mask, scale_k=scale_k, scale_v=scale_v,
                window=window)
    else:
        if block_table is not None:
            from repro.runtime.cache import gather_pages_dequant
            ck = gather_pages_dequant(ck, scale_k, block_table)
            cv = gather_pages_dequant(cv, scale_v, block_table)
        key_pos_b = jnp.broadcast_to(key_pos, (B, ck.shape[1]))
        if backend == "pallas":
            from repro.kernels import ops as kops
            o = kops.tree_attention(q, ck, cv, k_new, v_new, key_pos_b,
                                    pos_b, tree_depth, tree_mask,
                                    window=window)
        else:
            # dense part: W queries vs the KV cache (per-batch/query mask)
            cache_ok = batched_decode_mask(key_pos_b, positions, window)
            dense = cm.gqa_attend_partial(q, ck, cv, cache_ok[:, None], scale)
            # sparse part: W queries vs W fresh tree KVs, ancestor mask
            sparse = cm.gqa_attend_partial(q, k_new, v_new,
                                           tree_mask[None, None], scale)
            o = cm.merge_partials([dense, sparse]).astype(x.dtype)

    out = o.reshape(B, W, -1) @ p["wo"]
    return out, (k_new, v_new)


def attn_decode(cfg, p, x, *, ck, cv, key_pos, pos, window=0, backend="ref"):
    """Single-token decode: W=1 tree with a trivial mask.

    Note: the new token's K/V is returned for the caller to commit; attention
    includes it via the sparse part (self-attention to itself).
    """
    return attn_verify(
        cfg, p, x,
        ck=ck, cv=cv, key_pos=key_pos, pos=pos,
        tree_depth=jnp.zeros((1,), jnp.int32),
        tree_mask=jnp.ones((1, 1), bool),
        window=window, backend=backend)
