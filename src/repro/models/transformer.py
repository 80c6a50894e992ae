"""Decoder-only transformer stack (dense / MoE / VLM) with layer-scan.

Per-layer params are stacked on a leading L axis and the stack is traversed
with ``lax.scan`` (compile-time sanity for 94-layer configs).  Three paths:

  prefill  tokens/embeds (B,S)   -> logits (B,S,V), filled Cache
  decode   token (B,1) + Cache   -> logits (B,1,V), updated Cache
  verify   tree tokens (B,W)+Cache -> logits (B,W,V), uncommitted tree KVs

``commit`` scatters the accepted tree path's KVs into the cache (Ghidorah's
accept-then-fallback step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.attention import attn_decode, attn_init, attn_prefill, attn_verify
from repro.models.mlp import (EXPERT_WEIGHTS, mlp_apply, mlp_init, moe_apply,
                              moe_dropless, moe_init)
from repro.runtime.cache import (Cache, KVCache, PagedKVCache, _ring_match,
                                 init_kv_cache, kv_commit, paged_kv_write)


def init_params(cfg, rng):
    k_embed, k_layers, k_out = jax.random.split(rng, 3)
    dt = jnp.dtype(cfg.dtype)

    def layer_init(k):
        ka, km = jax.random.split(k)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dt),
            "ln2": jnp.ones((cfg.d_model,), dt),
            "attn": attn_init(cfg, ka),
        }
        p["moe" if cfg.num_experts else "mlp"] = (
            moe_init(cfg, km) if cfg.num_experts else mlp_init(cfg, km))
        return p

    params = {
        "embed": cm.embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dt),
        "layers": cm.stack_init(k_layers, cfg.num_layers, layer_init),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(k_out, cfg.d_model, cfg.padded_vocab, dt)
    return params


def _mix(cfg, lp, h):
    """Training MLP: (out, aux loss); experts with capacity drops."""
    if cfg.num_experts:
        return moe_apply(cfg, lp["moe"], h)
    return mlp_apply(cfg, lp["mlp"], h), jnp.zeros((), jnp.float32)


def _serve_mix(cfg, lp, h, stacks):
    """Serving MLP: (out, counts) — experts dropless, counts (picks,
    distinct experts touched) per layer; None for a dense MLP.  ``stacks``:
    the experts' weights of every layer (``_serve_layers``)."""
    if cfg.num_experts:
        return moe_dropless(cfg, lp["moe"], h, stacks)
    return mlp_apply(cfg, lp["mlp"], h), None


def _serve_layers(cfg, layers):
    """Serving's layer operands: (the scanned per-layer params, the
    experts' ``(L, E, ...)`` weight stacks or None).  A sparse-expert
    stack scans each layer's index in place of its experts, which the
    grouped matmul reads from the stacks in place: a scanned slice of them
    would copy all E experts of the layer every step."""
    if not cfg.num_experts:
        return layers, None
    moe = {k: v for k, v in layers["moe"].items() if k not in EXPERT_WEIGHTS}
    moe["layer"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    return dict(layers, moe=moe), {k: layers["moe"][k]
                                   for k in EXPERT_WEIGHTS}


def _layer_kinds(cfg):
    """Per-layer operands the layer scan carries beside the weights in a
    stack with ``layer_types``: each layer's window (0 = full) and rotary
    row (``common.rope_tables``).  None where every layer is alike — the
    scan then carries nothing more and the window is the cache's."""
    wins = cfg.layer_windows()
    if wins is None:
        return None
    inv, scale = cm.rope_tables(cfg)
    return {"window": jnp.asarray(wins, jnp.int32), "inv_freq": inv,
            "rope_scale": scale}


def _kind_args(kinds, window):
    """(window, rope) of one layer: its scanned row, else the stack's."""
    if kinds is None:
        return window, None
    return kinds["window"], (kinds["inv_freq"], kinds["rope_scale"])


def _moe_counts(counts):
    """Per-layer (picks, touched) of one forward -> int32 (3,): (1
    forward, picks, touched) summed over layers; None for dense stacks."""
    if counts is None:
        return None
    return jnp.concatenate([jnp.ones((1,), jnp.int32),
                            jnp.sum(counts, axis=0)])


def _logits(cfg, params, x):
    x = cm.rmsnorm(x, params["ln_f"], cfg.rmsnorm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head)[..., :cfg.vocab_size]


def embed_tokens(cfg, params, tokens):
    return params["embed"][tokens]


# --------------------------------------------------------------------------
def prefill(cfg, params, tokens=None, embeds=None, *, cache=None, window=0,
            max_len=None, return_cache=True, last_logits=False):
    """Returns (logits (B,S,V), extras, Cache).  ``embeds`` overrides token
    embedding (VLM path: pre-projected patch embeds + token embeds).
    ``max_len`` sets cache capacity (>= S + expected new tokens).
    ``return_cache=False`` (training) skips all KV-cache work."""
    x = embed_tokens(cfg, params, tokens) if embeds is None else embeds
    B, S, _ = x.shape
    eff_window = window                 # 0 = full attention; engine decides
    kinds = _layer_kinds(cfg)
    # a cache means serving: experts dropless, as in verify
    layers, stacks = _serve_layers(cfg, params["layers"]) if return_cache \
        else (params["layers"], None)

    def body(xc, xs):
        lp, kind = xs
        win, rope = _kind_args(kind, eff_window)
        a, (k, v) = attn_prefill(cfg, lp["attn"],
                                 cm.rmsnorm(xc, lp["ln1"], cfg.rmsnorm_eps),
                                 window=win, rope=rope)
        xc = xc + a
        h = cm.rmsnorm(xc, lp["ln2"], cfg.rmsnorm_eps)
        m, aux = _serve_mix(cfg, lp, h, stacks) if return_cache else \
            _mix(cfg, lp, h)
        kv_out = (k, v) if return_cache else ()
        return xc + m, (kv_out, aux)

    x, (kvs, auxs) = cm.layer_scan(cfg, body, x, (layers, kinds))
    # serving only needs the last position's next-token distribution; the
    # full (B, S, V) logits tensor (and its vocab-sharded collectives) is a
    # training-only cost.  See EXPERIMENTS.md SPerf hillclimb A.
    logits = _logits(cfg, params, x[:, -1:] if last_logits else x)
    if return_cache:
        extras = {"aux_loss": jnp.zeros((), jnp.float32), "hidden": x,
                  "moe": _moe_counts(auxs)}
    else:
        extras = {"aux_loss": jnp.sum(auxs), "hidden": x}

    if not return_cache:
        return logits, extras, None
    ks, vs = kvs
    if cache is None:
        cache = Cache(kv=init_kv_cache(
            cfg.num_layers, B, max(S, max_len or 0), cfg.num_kv_heads,
            cfg.head_dim, window=eff_window, dtype=jnp.dtype(cfg.dtype)))
    kv = _bulk_write(cache.kv, ks, vs, start=0)
    return logits, extras, Cache(kv=kv)


def _bulk_write(kv, ks, vs, start):
    """Write (L,B,S,Hkv,hd) KVs at [start_b, start_b + S) per sequence.

    ``start`` is a scalar (prefill: uniform positions) or (B,) per-sequence
    positions (decode after speculative steps, where positions diverge).
    Ring buffer keeps the tail when S exceeds the cache size.  Paged caches
    route through the block table (cache.paged_kv_write).
    """
    if isinstance(kv, PagedKVCache):
        return paged_kv_write(kv, ks, vs, start)
    B, S = ks.shape[1], ks.shape[2]
    size = kv.max_len
    off = 0
    if S >= size:                     # only the last `size` entries survive
        ks, vs = ks[:, :, -size:], vs[:, :, -size:]
        off, S = S - size, size
    start = jnp.asarray(start, jnp.int32)

    if start.ndim == 0:
        # uniform positions: one contiguous O(S_new) ring scatter shared by
        # the whole batch (prefill can be long — the per-sequence
        # gather+where path below would be O(S_cache * S_new))
        abs_pos = start + off + jnp.arange(S, dtype=jnp.int32)
        slots = abs_pos % size
        return KVCache(
            k=kv.k.at[:, :, slots].set(ks.astype(kv.k.dtype)),
            v=kv.v.at[:, :, slots].set(vs.astype(kv.v.dtype)),
            key_pos=kv.key_pos.at[:, slots].set(abs_pos),
            pos=jnp.full((B,), start + off + S, jnp.int32),
            window=kv.window)

    # diverged per-sequence positions: one ring-match per sequence, applied
    # to every layer's K and V and to key_pos (see cache._ring_match)
    def one(ck, cv, kp, kn, vn, st):
        # ck/cv: (L, S_cache, Hkv, hd); kn/vn: (L, S, Hkv, hd) one sequence
        abs_pos = st + off + jnp.arange(S, dtype=jnp.int32)
        written, src = _ring_match(abs_pos, jnp.ones((S,), bool), size)
        m = written[None, :, None, None]
        return (jnp.where(m, kn[:, src].astype(ck.dtype), ck),
                jnp.where(m, vn[:, src].astype(cv.dtype), cv),
                jnp.where(written, abs_pos[src], kp))

    k2, v2, kp2 = jax.vmap(one, in_axes=(1, 1, 0, 1, 1, 0),
                           out_axes=(1, 1, 0))(kv.k, kv.v, kv.key_pos,
                                               ks, vs, start)
    return KVCache(k=k2, v=v2, key_pos=kp2,
                   pos=start + off + S, window=kv.window)


# --------------------------------------------------------------------------
def verify(cfg, params, cache: Cache, tree_tokens, tree_depth, tree_mask,
           *, backend="ref", tree_kernel="dense"):
    """Tree-verification forward: W draft tokens vs cache + tree mask.

    Returns (logits (B,W,V), tree_kv (k,v each (L,B,W,Hkv,hd))).
    KVs are NOT committed — call ``commit`` with the accepted path.
    Quantized paged caches scan the per-layer scale slices alongside the
    pool so dequant happens inside each layer's attention; ``tree_kernel``
    selects the fused vs split (sparse tree kernel) paged verify path.
    """
    x = embed_tokens(cfg, params, tree_tokens)
    kv = cache.kv
    paged = isinstance(kv, PagedKVCache)
    table = kv.block_table if paged else None
    quantized = paged and kv.scale_k is not None
    layers, stacks = _serve_layers(cfg, params["layers"])

    def body(xc, xs):
        lp, kind, kvl = xs
        ck, cv = kvl[0], kvl[1]
        sk, sv = (kvl[2], kvl[3]) if len(kvl) == 4 else (None, None)
        win, rope = _kind_args(kind, kv.window)
        a, (k1, v1) = attn_verify(
            cfg, lp["attn"], cm.rmsnorm(xc, lp["ln1"], cfg.rmsnorm_eps),
            ck=ck, cv=cv, key_pos=kv.key_pos, pos=kv.pos,
            tree_depth=tree_depth, tree_mask=tree_mask,
            window=win, backend=backend, block_table=table,
            scale_k=sk, scale_v=sv, tree_kernel=tree_kernel, rope=rope)
        xc = xc + a
        m, counts = _serve_mix(cfg, lp,
                               cm.rmsnorm(xc, lp["ln2"], cfg.rmsnorm_eps),
                               stacks)
        return xc + m, ((k1, v1), counts)

    if paged:
        kv_scan = (kv.pool_k, kv.pool_v)
        if quantized:
            kv_scan += (kv.scale_k, kv.scale_v)
    else:
        kv_scan = (kv.k, kv.v)
    x, ((k_new, v_new), counts) = cm.layer_scan(
        cfg, body, x, (layers, _layer_kinds(cfg), kv_scan))
    extras = {"tree_kv": (k_new, v_new), "hidden": x,
              "moe": _moe_counts(counts)}
    return _logits(cfg, params, x), extras


def decode(cfg, params, cache: Cache, tokens, *, backend="ref"):
    """Plain 1-token decode (the Sequential baseline step).

    tokens: (B, 1).  Returns (logits (B,1,V), updated Cache).
    """
    logits, extras = verify(
        cfg, params, cache, tokens,
        tree_depth=jnp.zeros((1,), jnp.int32),
        tree_mask=jnp.ones((1, 1), bool),
        backend=backend)
    k1, v1 = extras["tree_kv"]
    kv = _bulk_write(cache.kv, k1, v1, start=cache.kv.pos)
    return logits, Cache(kv=kv)


def commit(cfg, cache: Cache, extras, accept_nodes, n_accept, max_depth):
    """Scatter each sequence's accepted tree path at [pos_b, pos_b + n_b).

    accept_nodes: (B, Dmax) node indices of the accepted paths (padded);
    n_accept: (B,) accepted tokens per sequence (1..Dmax).
    Writes are masked per sequence: slots beyond n_accept[b] keep their
    previous contents (the vmapped ring scatter lives in cache.kv_commit).
    """
    tree_kv = extras["tree_kv"] if isinstance(extras, dict) else extras
    k_new, v_new = tree_kv                                   # (L,B,W,Hkv,hd)
    return Cache(kv=kv_commit(cache.kv, k_new, v_new, accept_nodes,
                              n_accept, max_depth))
