"""Pallas TPU kernel: fused tree-verification attention (dense and paged).

The Ghidorah dense/sparse split, TPU-native (DESIGN.md §2): W draft queries
attend to the KV cache (dense part, tiled over KV blocks in VMEM) and to the
W fresh tree KVs under the ancestor mask (sparse part, VMEM-resident), with
a single online-softmax accumulator carried across the grid — the kernel
form of the paper's Eq.-1 online-softmax merge.

Layout: one (batch, kv-head group) pair per grid row.  A K/V block carries
``hg`` whole kv heads (``_head_group``), so the block's two minor dims are
``(hg, hd)`` — the whole ``(Hkv, hd)`` of the cache, or 8 heads of it —
which is what the TPU block-tiling rule accepts; the body loops over the
group's heads.  Queries are grouped (G = Hq/Hkv rows per kv head) so each
head's score matmul is (G*W, hd) x (hd, BS).

The small per-row operands ride in layouts whose two minor block dims equal
the array's: key positions as ``(B, nblocks, 1, BS)`` rows, query positions
and window bounds as ``(B, G*W, 1)`` columns already expanded over the G
query groups, the tree mask as ``(G*W, W)`` int32, and the int8 dequant
scales as ``(n_pages + 1, Hkv, 1)``.

Grid: (B, Hkv // hg, nblocks+1); the last block handles the tree part and
the normalization + writeback.  Scratch (o, m, l) persists across the
KV-block axis (sequential minor-most grid dimension on TPU).

Paged variant (``paged_tree_attention``): the KV blocks live in a SHARED
page pool ``(n_pages + 1, page_size, Hkv, hd)`` instead of per-sequence
rows.  The grid's KV axis loops over a sequence's *logical* pages and the
block table rides in as a scalar-prefetch argument, so the index map DMAs
physical page ``table[b, i]`` for grid step ``i`` — unreserved entries are
pre-clamped to the trailing trash page, whose slots carry ``key_pos == -1``
and mask to zero weight.  The kernel body is the dense one; only the
BlockSpec index maps change.

Every wrapper takes ``interpret`` with no default: ``kernels/ops.py``
resolves it from the backend (interpreted on the CPU, compiled on a TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_S = 512


def _head_group(Hkv):
    """KV heads per grid step: all of them, or 8 when Hkv is a multiple of
    8.  Either satisfies the tiling rule on the block's (heads, hd) minor
    dims, and groups of 8 keep a 32-head cache's double-buffered K/V
    blocks inside the scoped VMEM budget at block_s=512."""
    return 8 if Hkv % 8 == 0 else Hkv


def _row_operands(key_pos, q_pos, lo, G, bs):
    """Per-row operands in tiling-legal layouts: key positions (B, S) ->
    (B, S // bs, 1, bs); query positions and window bounds (B, W) ->
    (B, G*W, 1) columns, row g*W + w carrying node w (the grouped query
    row order)."""
    B, S = key_pos.shape
    kpos = key_pos.astype(jnp.int32).reshape(B, S // bs, 1, bs)
    qcol = jnp.tile(q_pos.astype(jnp.int32), (1, G))[..., None]
    locol = jnp.tile(lo.astype(jnp.int32), (1, G))[..., None]
    return kpos, qcol, locol


def _group_queries(q, Hkv):
    """(B, W, Hq, hd) -> (B, Hkv, G*W, hd), row g*W + w."""
    B, W, Hq, hd = q.shape
    G = Hq // Hkv
    return q.reshape(B, W, Hkv, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, G * W, hd)


def _ungroup(out, W):
    """(B, Hkv, G*W, hd) -> (B, W, Hq, hd)."""
    B, Hkv, GW, hd = out.shape
    G = GW // W
    return out.reshape(B, Hkv, G, W, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, W, Hkv * G, hd)


def _group_mask(tree_mask, G):
    """(W, W) bool ancestor mask -> (G*W, W) int32, one copy per group."""
    return jnp.tile(tree_mask.astype(jnp.int32), (G, 1))


def _scores(q, k):
    """(R, hd) x (T, hd) -> (R, T), contracting hd without a transpose."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _pv(p, v):
    return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _cache_ok(kpos_ref, qpos_ref, lo_ref):
    """(G*W, BS) validity of this KV block's slots for every query row."""
    kpos = kpos_ref[0, 0]                          # (1, BS)
    qpos = qpos_ref[0]                             # (G*W, 1)
    lo = lo_ref[0]
    return (kpos >= 0) & (kpos <= qpos) & (kpos > lo)


def _kv_head(ref, h, s_ref):
    """Head ``h`` of a (1, T, hg, hd) K/V block as (T, hd) float32,
    dequantized by the page's (layer, head) scale when one is given."""
    x = ref[0, :, h, :].astype(jnp.float32)
    if s_ref is not None:
        x = x * s_ref[0, h, 0]
    return x


def _kernel(q_ref, ck_ref, cv_ref, kn_ref, vn_ref, kpos_ref, qpos_ref,
            lo_ref, mask_ref, o_ref, o_acc, m_acc, l_acc, *, nblocks, scale,
            sk_ref=None, sv_ref=None):
    i = pl.program_id(2)
    hg = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def online_update(h, k, v, valid):
        """k, v: (T, hd); valid: (G*W, T) bool."""
        q = q_ref[0, h].astype(jnp.float32)        # (G*W, hd)
        s = jnp.where(valid, _scores(q, k) * scale, NEG_INF)
        m_prev = m_acc[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[h] = l_acc[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[h] = o_acc[h] * corr + _pv(p, v)
        m_acc[h] = m_new

    @pl.when(i < nblocks)
    def _cache_block():
        # fused dequant: the page's per-(layer, head) scale rides the same
        # table-driven index map as the page (1.0 for float pools, so the
        # multiply is exact there)
        ok = _cache_ok(kpos_ref, qpos_ref, lo_ref)
        for h in range(hg):
            online_update(h, _kv_head(ck_ref, h, sk_ref),
                          _kv_head(cv_ref, h, sv_ref), ok)

    @pl.when(i == nblocks)
    def _tree_block():
        ok = mask_ref[...] != 0                    # (G*W, W)
        for h in range(hg):
            online_update(h, _kv_head(kn_ref, h, None),
                          _kv_head(vn_ref, h, None), ok)
            l_safe = jnp.maximum(l_acc[h], 1e-30)
            o_ref[0, h] = (o_acc[h] / l_safe).astype(o_ref.dtype)


def _scratch(hg, GW, hd):
    return [pltpu.VMEM((hg, GW, hd), jnp.float32),   # o accumulator
            pltpu.VMEM((hg, GW, 1), jnp.float32),    # running max m
            pltpu.VMEM((hg, GW, 1), jnp.float32)]    # running sum l


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def tree_attention(q, ck, cv, k_new, v_new, key_pos, q_pos, lo, tree_mask,
                   *, block_s=DEFAULT_BLOCK_S, interpret):
    """See ref.tree_attention_ref for semantics.  q: (B, W, Hq, hd);
    key_pos: (B, S); q_pos/lo: (B, W) — per-sequence position rows (batched
    speculative decoding leaves each sequence at its own absolute position)."""
    B, W, Hq, hd = q.shape
    S, Hkv = ck.shape[1], ck.shape[2]
    G = Hq // Hkv
    hg = _head_group(Hkv)
    nh = Hkv // hg

    # pad cache length to a block multiple; padded slots get key_pos = -1
    bs = min(block_s, max(S, 1))
    pad = (-S) % bs
    if pad:
        ck = jnp.pad(ck, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        key_pos = jnp.pad(key_pos, ((0, 0), (0, pad)), constant_values=-1)
    nblocks = (S + pad) // bs
    kpos, qcol, locol = _row_operands(key_pos, q_pos, lo, G, bs)

    out = pl.pallas_call(
        functools.partial(_kernel, nblocks=nblocks, scale=hd ** -0.5),
        grid=(B, nh, nblocks + 1),
        in_specs=[
            pl.BlockSpec((1, hg, G * W, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, hg, hd),
                         lambda b, h, i, _n=nblocks: (b, jnp.minimum(i, _n - 1), h, 0)),
            pl.BlockSpec((1, bs, hg, hd),
                         lambda b, h, i, _n=nblocks: (b, jnp.minimum(i, _n - 1), h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h, i: (b, 0, h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h, i: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, bs),
                         lambda b, h, i, _n=nblocks: (b, jnp.minimum(i, _n - 1), 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((G * W, W), lambda b, h, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, G * W, hd), lambda b, h, i: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd), q.dtype),
        scratch_shapes=_scratch(hg, G * W, hd),
        interpret=interpret,
        name="tree_attention",
    )(_group_queries(q, Hkv), ck, cv, k_new, v_new, kpos, qcol, locol,
      _group_mask(tree_mask, G))
    return _ungroup(out, W)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_tree_attention(q, pool_k, pool_v, scale_k, scale_v, k_new, v_new,
                         block_table, key_pos, q_pos, lo, tree_mask, *,
                         interpret):
    """Paged tree-verification attention: the KV-block grid axis walks a
    sequence's block table instead of a dense row.

    q: (B, W, Hq, hd); pool_k/pool_v: (n_pages + 1, ps, Hkv, hd) one
    layer's shared pool, trash page last; scale_k/scale_v: (n_pages + 1,
    Hkv) per-page dequant scales — all-ones for float pools, so the fused
    multiply is exact there; block_table: (B, max_pages) int32 (-1 =
    unreserved); key_pos: (B, max_pages * ps); q_pos/lo: (B, W).
    One KV "block" is one page (block_s == page_size): grid step i of row b
    fetches physical page ``table[b, i]`` via scalar prefetch, and the
    page's scale block rides the same table-driven index map.
    """
    B, W, Hq, hd = q.shape
    P, ps, Hkv = pool_k.shape[0], pool_k.shape[1], pool_k.shape[2]
    maxp = block_table.shape[1]
    G = Hq // Hkv
    hg = _head_group(Hkv)
    nh = Hkv // hg
    # unreserved logical pages fetch the trash page; their slots are
    # key_pos == -1, so the validity mask zeroes them
    tbl = jnp.where(block_table < 0, P - 1, block_table).astype(jnp.int32)
    kpos, qcol, locol = _row_operands(key_pos, q_pos, lo, G, ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nh, maxp + 1),
        in_specs=[
            pl.BlockSpec((1, hg, G * W, hd), lambda b, h, i, t: (b, h, 0, 0)),
            pl.BlockSpec((1, ps, hg, hd),
                         lambda b, h, i, t, _n=maxp:
                         (t[b, jnp.minimum(i, _n - 1)], 0, h, 0)),
            pl.BlockSpec((1, ps, hg, hd),
                         lambda b, h, i, t, _n=maxp:
                         (t[b, jnp.minimum(i, _n - 1)], 0, h, 0)),
            pl.BlockSpec((1, hg, 1),
                         lambda b, h, i, t, _n=maxp:
                         (t[b, jnp.minimum(i, _n - 1)], h, 0)),
            pl.BlockSpec((1, hg, 1),
                         lambda b, h, i, t, _n=maxp:
                         (t[b, jnp.minimum(i, _n - 1)], h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h, i, t: (b, 0, h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h, i, t: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda b, h, i, t, _n=maxp:
                         (b, jnp.minimum(i, _n - 1), 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i, t: (b, 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i, t: (b, 0, 0)),
            pl.BlockSpec((G * W, W), lambda b, h, i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, G * W, hd),
                               lambda b, h, i, t: (b, h, 0, 0)),
        scratch_shapes=_scratch(hg, G * W, hd),
    )

    def kernel(tbl_ref, q_ref, ck_ref, cv_ref, sk_ref, sv_ref, kn_ref,
               vn_ref, kpos_ref, qpos_ref, lo_ref, mask_ref, o_ref,
               o_acc, m_acc, l_acc):
        # table only drives the index maps; the body is the dense kernel
        # with the per-page dequant scales threaded in
        _kernel(q_ref, ck_ref, cv_ref, kn_ref, vn_ref, kpos_ref, qpos_ref,
                lo_ref, mask_ref, o_ref, o_acc, m_acc, l_acc,
                nblocks=maxp, scale=hd ** -0.5,
                sk_ref=sk_ref, sv_ref=sv_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd), q.dtype),
        interpret=interpret,
        name="paged_tree_attention",
    )(tbl, _group_queries(q, Hkv), pool_k, pool_v,
      scale_k.astype(jnp.float32)[..., None],
      scale_v.astype(jnp.float32)[..., None], k_new, v_new, kpos, qcol,
      locol, _group_mask(tree_mask, G))
    return _ungroup(out, W)


def _cache_partial_kernel(q_ref, ck_ref, cv_ref, sk_ref, sv_ref, kpos_ref,
                          qpos_ref, lo_ref, o_ref, o_acc, m_acc, l_acc, *,
                          nblocks, scale):
    """Cache-only half of the verify attention, emitting UNNORMALIZED
    online-softmax partials packed into one (G*W, hd + 2) block per head —
    o in [:, :hd], running max m at [:, hd], sum l at [:, hd + 1].  Packing
    into a single output keeps the wrapper a one-``pallas_call``/
    one-BlockSpec shape the R8 bounds extractor can verify; the wrapper
    unpacks to the ``cm.merge_partials`` layout so the sparse tree half (or
    a sequence shard) merges with the usual Eq.-1 rule."""
    i = pl.program_id(2)
    hg = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    ok = _cache_ok(kpos_ref, qpos_ref, lo_ref)     # (G*W, ps)
    for h in range(hg):
        q = q_ref[0, h].astype(jnp.float32)        # (G*W, hd)
        k = _kv_head(ck_ref, h, sk_ref)
        v = _kv_head(cv_ref, h, sv_ref)
        s = jnp.where(ok, _scores(q, k) * scale, NEG_INF)
        m_prev = m_acc[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[h] = l_acc[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[h] = o_acc[h] * corr + _pv(p, v)
        m_acc[h] = m_new

    @pl.when(i == nblocks - 1)
    def _emit():
        # all-masked rows: clamp m like the oracle's m_safe so partials
        # compare exactly (l stays 0, so the merge ignores them anyway)
        for h in range(hg):
            m_safe = jnp.maximum(m_acc[h], NEG_INF / 2)
            o_ref[0, h] = jnp.concatenate([o_acc[h], m_safe, l_acc[h]],
                                          axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_cache_attention(q, pool_k, pool_v, scale_k, scale_v, block_table,
                          key_pos, q_pos, lo, *, interpret):
    """Cache-only paged page walk (the dense half of the verify split when
    the W×W tree half runs as ``sparse_tree_attention_partial``).

    Same operands as ``paged_tree_attention`` minus the tree ones; the grid
    is (B, Hkv // hg, max_pages) — no trailing tree block.  Returns merge
    partials ``(o (B, W, Hq, hd) f32 unnormalized, m (B, Hq, W),
    l (B, Hq, W))`` in the ``cm.merge_partials`` layout.
    """
    B, W, Hq, hd = q.shape
    P, ps, Hkv = pool_k.shape[0], pool_k.shape[1], pool_k.shape[2]
    maxp = block_table.shape[1]
    G = Hq // Hkv
    hg = _head_group(Hkv)
    nh = Hkv // hg
    tbl = jnp.where(block_table < 0, P - 1, block_table).astype(jnp.int32)
    kpos, qcol, locol = _row_operands(key_pos, q_pos, lo, G, ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nh, maxp),
        in_specs=[
            pl.BlockSpec((1, hg, G * W, hd), lambda b, h, i, t: (b, h, 0, 0)),
            pl.BlockSpec((1, ps, hg, hd),
                         lambda b, h, i, t: (t[b, i], 0, h, 0)),
            pl.BlockSpec((1, ps, hg, hd),
                         lambda b, h, i, t: (t[b, i], 0, h, 0)),
            pl.BlockSpec((1, hg, 1), lambda b, h, i, t: (t[b, i], h, 0)),
            pl.BlockSpec((1, hg, 1), lambda b, h, i, t: (t[b, i], h, 0)),
            pl.BlockSpec((1, 1, 1, ps), lambda b, h, i, t: (b, i, 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i, t: (b, 0, 0)),
            pl.BlockSpec((1, G * W, 1), lambda b, h, i, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, G * W, hd + 2),
                               lambda b, h, i, t: (b, h, 0, 0)),
        scratch_shapes=_scratch(hg, G * W, hd),
    )

    def kernel(tbl_ref, *refs):
        _cache_partial_kernel(*refs, nblocks=maxp, scale=hd ** -0.5)

    packed = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd + 2), jnp.float32),
        interpret=interpret,
        name="paged_cache_attention",
    )(tbl, _group_queries(q, Hkv), pool_k, pool_v,
      scale_k.astype(jnp.float32)[..., None],
      scale_v.astype(jnp.float32)[..., None], kpos, qcol, locol)
    pk = packed.reshape(B, Hkv, G, W, hd + 2)
    o = pk[..., :hd].transpose(0, 3, 1, 2, 4).reshape(B, W, Hq, hd)
    m = pk[..., hd].reshape(B, Hkv * G, W)
    l = pk[..., hd + 1].reshape(B, Hkv * G, W)
    return o, m, l
