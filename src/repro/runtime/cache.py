"""Decode-state caches: dense and paged KV caches, Mamba2 SSM state, xLSTM
states, and encoder cross-attention memory.

Two KV layouts share one logical addressing convention:

Dense (``KVCache``)
-------------------
- KV arrays are stacked over layers: ``(L, B, S, Hkv, hd)`` so model stacks
  can ``lax.scan`` over the leading axis.  Every sequence owns a full
  ``S = max_len`` row; with a sliding window the row is a ring buffer:
  slot(p) = p % S.
- Still the layout for sliding-window caches (the ring IS the window) and
  the parity baseline for the paged path (``paged=False`` engines).

Paged (``PagedKVCache``)
------------------------
- One shared pool of fixed-size pages ``(L, n_pages + 1, page_size, Hkv,
  hd)``; **page ``n_pages`` is a trash page** — every masked, unreserved, or
  overflowing write is redirected there, so a row can never scribble on a
  page another row owns.
- A per-sequence ``block_table (B, max_pages)`` maps *logical* page
  ``s // page_size`` to a physical pool page (-1 = unreserved).  Logical
  slot ``s = pos % (max_pages * page_size)`` — the same ring arithmetic as
  the dense path, so masks/kernels are layout-agnostic.
- Reservation is page-grained: admission allocates
  ``ceil((prompt + budget + overshoot) / page_size)`` pages from a host-side
  free list (``PageAllocator``), eviction frees them.  ``capacity_left`` =
  reserved slots minus ``pos``; a row that outgrows its reservation freezes
  (shortfall reported in ``n_emitted``) instead of corrupting a neighbor.
- Diverged-length sequences therefore share one slot pool: a short request
  reserves 2-3 pages while a long one reserves dozens, and resident batch at
  fixed pool memory is bounded by actual context, not ``B * max_len``.

Shared conventions
------------------
- ``key_pos (B, S_logical)`` holds the absolute position stored in each
  logical slot (-1 = empty), **per sequence**.  The attention mask is
  derived from ``key_pos`` (validity + causality + window), so ring
  wraparound and unreserved paged slots need no special-casing.
- ``pos (B,)`` is the number of tokens processed so far **per sequence**.
  Batched speculative decoding accepts a different number of draft tokens
  per sequence each step, so positions diverge across the batch; every
  write and mask below is per-sequence.
- RoPE is applied to keys at *write* time with their absolute position.

Quantized int8 pages (``kv_dtype=int8``)
----------------------------------------
The paged pool may store KV in **symmetric per-page int8**: ``pool_k`` /
``pool_v`` become int8 and the cache carries ``scale_k`` / ``scale_v``
float32 tensors of shape ``(L, n_pages + 1, Hkv)`` — one scale per (layer,
pool page, kv head), trash page included.  Format and error model:

- **Arming.** A page's scale starts at the 0.0 *unarmed* sentinel.  The
  first write into the page arms it: scale = amax(|x| over that write's
  entries landing in the page, per (layer, head)) / 127.  The scale is
  then FROZEN while the page is resident — re-arming on later writes
  would silently re-scale entries already quantized under the old scale.
- **Saturation.** Later writes quantize with the frozen scale and clamp:
  ``q = clip(round(x / scale), -127, 127)`` (an unarmed 0.0 scale stores
  0).  K/V magnitudes are close to position-stationary per (layer, head),
  so the first-write amax is a good page-lifetime range estimate; an
  outlier later in the page saturates instead of corrupting neighbors.
- **Dequant.** ``x' = q * scale``, fused into the Pallas page walk (the
  scale rides the scalar-prefetched block-table path next to the page
  index) and mirrored by ``gather_pages_dequant`` on the ref backend.
- **Error bound.** Within the armed range the absolute error per element
  is <= scale/2 = amax/254 (relative ~0.4% of the page's per-head peak).
  End-to-end the engines hold attention outputs to the tolerances
  documented in ``tests/test_kernels.py`` / ``tests/test_paged.py``.
- **Recycling.** A page's scale is zeroed-then-re-armed exactly when a
  fresh reservation installs it (``_paged_insert_row`` / admission).
  ``reset_rows`` leaves pool scales alone: a freed page's armed scale is
  unreachable garbage (like its int8 payload), and the dead row's block
  table is stale by the time the scheduler batches resets — the page may
  already carry a same-boundary admission whose scale must survive.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


@partial(jax.tree_util.register_dataclass,
         data_fields=["k", "v", "key_pos", "pos"], meta_fields=["window"])
@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (L, B, S, Hkv, hd)
    v: jax.Array          # (L, B, S, Hkv, hd)
    key_pos: jax.Array    # (B, S) int32 absolute position per slot; -1 empty
    pos: jax.Array        # (B,) int32 tokens processed so far per sequence
    window: int = 0       # static: 0 = full attention; >0 = sliding window

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


@partial(jax.tree_util.register_dataclass,
         data_fields=["pool_k", "pool_v", "block_table", "key_pos", "pos",
                      "scale_k", "scale_v"],
         meta_fields=["page_size", "window"])
@dataclasses.dataclass
class PagedKVCache:
    """Block-table KV cache: one shared page pool + per-sequence tables.

    ``pool_k/pool_v`` carry ``n_pages`` real pages plus one trailing *trash*
    page; writes whose logical slot is masked or falls on an unreserved
    table entry land in the trash page (see ``_pool_scatter``), never in a
    page another sequence reserved.  ``window`` is kept for interface parity
    with ``KVCache`` but must be 0 — sliding-window caches stay dense (the
    ring IS the window).

    When the pool dtype is int8 the cache is *quantized*: ``scale_k`` /
    ``scale_v (L, n_pages + 1, Hkv)`` hold the symmetric per-page dequant
    scales (see the module docstring for the arming/freezing error model);
    for float pools they are None and every code path below is unchanged.
    """
    pool_k: jax.Array       # (L, n_pages + 1, page_size, Hkv, hd)
    pool_v: jax.Array       # (L, n_pages + 1, page_size, Hkv, hd)
    block_table: jax.Array  # (B, max_pages) int32 physical page id; -1 free
    key_pos: jax.Array      # (B, max_pages * page_size) int32; -1 empty
    pos: jax.Array          # (B,) int32 tokens processed so far per sequence
    scale_k: Optional[jax.Array] = None   # (L, n_pages + 1, Hkv) f32 | None
    scale_v: Optional[jax.Array] = None   # (L, n_pages + 1, Hkv) f32 | None
    page_size: int = 16     # static: slots per page
    window: int = 0         # static: always 0 (full attention only)

    @property
    def quantized(self) -> bool:
        return self.pool_k.dtype == jnp.int8

    @property
    def max_len(self) -> int:
        """Logical row length (ring size) — max_pages * page_size."""
        return self.key_pos.shape[1]

    @property
    def n_pages(self) -> int:
        """Real (reservable) pages — excludes the trash page."""
        return self.pool_k.shape[1] - 1

    @property
    def max_pages(self) -> int:
        return self.block_table.shape[1]


@partial(jax.tree_util.register_dataclass,
         data_fields=["ssm", "conv", "pos"], meta_fields=[])
@dataclasses.dataclass
class MambaState:
    ssm: jax.Array        # (L, B, nh, hd, N) float32
    conv: jax.Array       # (L, B, K-1, C)    conv tail (C = di + 2N)
    pos: jax.Array        # (B,) int32


@partial(jax.tree_util.register_dataclass,
         data_fields=["layers", "pos"], meta_fields=[])
@dataclasses.dataclass
class XLSTMState:
    layers: tuple         # per-layer dict of state arrays (unrolled stack)
    pos: jax.Array        # (B,) int32


@partial(jax.tree_util.register_dataclass,
         data_fields=["kv", "mamba", "xlstm", "cross_k", "cross_v"],
         meta_fields=[])
@dataclasses.dataclass
class Cache:
    """Union cache for all architecture families (unused fields = None)."""
    kv: Optional[KVCache] = None            # self-attention layers
    mamba: Optional[MambaState] = None      # Mamba2 layers
    xlstm: Optional[XLSTMState] = None      # xLSTM layers
    cross_k: Optional[jax.Array] = None     # (L, B, Senc, Hkv, hd) enc-dec
    cross_v: Optional[jax.Array] = None

    @property
    def pos(self) -> jax.Array:
        for c in (self.kv, self.mamba, self.xlstm):
            if c is not None:
                return c.pos
        raise ValueError("empty cache")


# --------------------------------------------------------------------------
def init_kv_cache(n_layers, batch, max_len, n_kv, head_dim, *, window=0,
                  dtype=jnp.bfloat16) -> KVCache:
    size = min(max_len, window) if window else max_len
    return KVCache(
        k=jnp.zeros((n_layers, batch, size, n_kv, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, size, n_kv, head_dim), dtype),
        key_pos=jnp.full((batch, size), -1, jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        window=window,
    )


def init_paged_kv_cache(n_layers, batch, max_len, n_kv, head_dim, *,
                        page_size, n_pages,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    """Empty paged bank: zeroed pool (+1 trash page), all tables unreserved.

    ``max_len`` is the *logical* per-row capacity (rounded up to whole
    pages); the physical pool holds ``n_pages`` reservable pages shared by
    all ``batch`` rows.  ``dtype=jnp.int8`` builds a quantized pool with
    zeroed (unarmed) per-page scale tensors.
    """
    max_pages = pages_for(max_len, page_size)
    quantized = jnp.dtype(dtype) == jnp.int8

    def _scale():
        # one DISTINCT buffer per call: scale_k/scale_v sharing one array
        # would donate the same buffer twice in the state-threading jits
        return (jnp.zeros((n_layers, n_pages + 1, n_kv), jnp.float32)
                if quantized else None)

    return PagedKVCache(
        pool_k=jnp.zeros((n_layers, n_pages + 1, page_size, n_kv, head_dim),
                         dtype),
        pool_v=jnp.zeros((n_layers, n_pages + 1, page_size, n_kv, head_dim),
                         dtype),
        block_table=jnp.full((batch, max_pages), -1, jnp.int32),
        key_pos=jnp.full((batch, max_pages * page_size), -1, jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        scale_k=_scale(),
        scale_v=_scale(),
        page_size=page_size,
    )


def pages_for(n_tokens, page_size) -> int:
    """Pages needed to hold ``n_tokens`` slots."""
    return -(-int(n_tokens) // int(page_size))


def page_bytes(n_layers, page_size, n_kv, head_dim, kv_dtype) -> int:
    """Device bytes one pool page costs across all layers, K+V, INCLUDING
    the per-page scale overhead when quantized — the honest denominator for
    fixed-pool-bytes comparisons (sched_bench, admission sizing)."""
    elt = jnp.dtype(kv_dtype).itemsize
    data = 2 * n_layers * page_size * n_kv * head_dim * elt
    scale = 2 * n_layers * n_kv * 4 if jnp.dtype(kv_dtype) == jnp.int8 else 0
    return data + scale


def kv_bytes_per_token(n_layers, n_kv, head_dim, kv_dtype, page_size) -> float:
    """Bytes per reservable token slot (K+V, all layers, amortized scale)."""
    return page_bytes(n_layers, page_size, n_kv, head_dim, kv_dtype) \
        / page_size


def pages_at_fixed_bytes(budget_bytes, n_layers, page_size, n_kv, head_dim,
                         kv_dtype) -> int:
    """Reservable pages a byte budget funds at ``kv_dtype`` — the engine
    admission-sizing hook that turns the int8 bytes-per-token saving into
    extra reservable tokens at FIXED pool memory."""
    return int(budget_bytes) // page_bytes(n_layers, page_size, n_kv,
                                           head_dim, kv_dtype)


class PageAllocator:
    """Host-side free list over the pool's reservable page ids.

    Alloc/free happen only at admission/eviction/abort boundaries (and once
    per ``generate`` call), so this never syncs the device.  Pages are
    handed out lowest-id-first so runs are deterministic and reuse after
    fragmented frees is exercised by the unit tests.

    Every page handed out is tracked in a held set, so ``outstanding`` /
    ``conserved`` give the leak audit the fault paths rely on: after any
    mix of evictions, mid-flight aborts and replica-crash cleanups,
    ``available + outstanding == n_pages`` must hold at every step and a
    drained bank must return to ``available == n_pages`` — a page that is
    neither free nor held by a row is a leak.  Freeing a page that is not
    currently held (double free, foreign page) raises instead of
    corrupting the free list.
    """

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self._free = list(range(self.n_pages))   # kept sorted
        self._held = set()                        # pages currently reserved

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def outstanding(self) -> int:
        """Pages currently reserved by rows (the held side of the audit)."""
        return len(self._held)

    @property
    def conserved(self) -> bool:
        """free + held == pool, with no page on both sides — the invariant
        every admission/eviction/abort sequence must preserve."""
        return (len(self._free) + len(self._held) == self.n_pages
                and not self._held.intersection(self._free))

    def alloc(self, n: int) -> list:
        """Take exactly ``n`` pages; raises if the pool cannot supply them
        (callers gate on ``available`` / ``alloc_upto`` for partial)."""
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        pages, self._free = self._free[:n], self._free[n:]
        self._held.update(pages)
        return pages

    def alloc_upto(self, n: int) -> list:
        """Take ``min(n, available)`` pages (partial reservations freeze at
        ``capacity_left`` instead of failing)."""
        return self.alloc(min(n, len(self._free)))

    def free(self, pages) -> None:
        for p in pages:
            p = int(p)
            if p < 0:
                continue
            if p not in self._held:
                raise RuntimeError(f"bad page free: {p}")
            self._held.discard(p)
            self._free.append(p)
        self._free.sort()


def _arm_and_quantize(src_flat, scale, flat_page, P):
    """Quantize one operand's writes under frozen-first-write page scales.

    src_flat: (L, N, Hkv, hd) float sources; scale: (L, P, Hkv) with 0.0 =
    unarmed; flat_page: (N,) destination pool page per write.  Pages
    UNARMED before this op arm to amax(|writes into the page|)/127 per
    (layer, head); already-armed pages keep their scale and later writes
    saturate (module docstring: re-arming would mis-scale entries already
    stored under the old scale).  Returns (q (L, N, Hkv, hd) int8,
    new_scale (L, P, Hkv)).
    """
    src_flat = src_flat.astype(jnp.float32)
    amax = jnp.max(jnp.abs(src_flat), axis=-1)               # (L, N, Hkv)
    page_amax = jax.ops.segment_max(jnp.moveaxis(amax, 1, 0), flat_page,
                                    num_segments=P)          # (P, L, Hkv)
    page_amax = jnp.maximum(jnp.moveaxis(page_amax, 0, 1), 0.0)
    new_scale = jnp.where(scale > 0.0, scale, page_amax / 127.0)
    s_w = new_scale[:, flat_page]                            # (L, N, Hkv)
    s_w = s_w[..., None]
    q = jnp.where(s_w > 0.0,
                  jnp.clip(jnp.round(src_flat
                                     / jnp.where(s_w > 0.0, s_w, 1.0)),
                           -127.0, 127.0),
                  0.0)
    return q.astype(jnp.int8), new_scale


def _pool_scatter(pool_k, pool_v, tables, k_src, v_src, abs_pos, valid,
                  scale_k=None, scale_v=None):
    """Scatter per-sequence writes through block tables into the shared pool.

    pool_k/pool_v: (L, P, ps, Hkv, hd) with P = n_pages + 1 (trash last);
    tables: (B, max_pages); k_src/v_src: (L, B, W, Hkv, hd);
    abs_pos/valid: (B, W) absolute positions and write mask;
    scale_k/scale_v: (L, P, Hkv) per-page dequant scales when the pool is
    int8 (None = float pool, stored verbatim).

    Masked writes, and writes whose logical page is unreserved (table entry
    -1 — e.g. a partially-reserved row that outgrew its pages), are
    redirected to the trash page: a row can NEVER overwrite a page it does
    not own (a rejected write's magnitude only ever arms the never-read
    trash scale).  Returns (pool_k, pool_v, scale_k, scale_v, ok (B, W))
    where ``ok`` marks the writes that landed in real pages (callers mark
    only those in key_pos).
    """
    L, P, ps, Hkv, hd = pool_k.shape
    s_log = tables.shape[1] * ps
    logical = abs_pos % s_log                                # (B, W)
    page = jnp.take_along_axis(tables, logical // ps, axis=1)
    ok = valid & (page >= 0)
    phys = jnp.where(ok, page * ps + logical % ps, P * ps - 1)
    flat = phys.reshape(-1)                                  # (B*W,)
    k_flat = k_src.reshape(L, -1, Hkv, hd)
    v_flat = v_src.reshape(L, -1, Hkv, hd)
    if scale_k is not None:
        k_flat, scale_k = _arm_and_quantize(k_flat, scale_k, flat // ps, P)
        v_flat, scale_v = _arm_and_quantize(v_flat, scale_v, flat // ps, P)
    pk = pool_k.reshape(L, P * ps, Hkv, hd)
    pv = pool_v.reshape(L, P * ps, Hkv, hd)
    pk = pk.at[:, flat].set(k_flat.astype(pk.dtype))
    pv = pv.at[:, flat].set(v_flat.astype(pv.dtype))
    return (pk.reshape(pool_k.shape), pv.reshape(pool_v.shape),
            scale_k, scale_v, ok)


def _zero_page_scales(scale, pages, mask):
    """Zero (un-arm) the per-page scales of the pool pages in ``pages``
    where ``mask`` holds.  scale: (L, P, Hkv); pages: int page ids (-1 =
    unreserved); mask broadcastable to pages.  Non-targets redirect to the
    trash page, whose scale is never read."""
    P = scale.shape[1]
    tgt = jnp.where(mask & (pages >= 0), pages, P - 1).reshape(-1)
    return scale.at[:, tgt].set(0.0)


def _keypos_scatter(key_pos, abs_pos, ok):
    """Mark ``abs_pos`` at its logical slot where ``ok``; rejected writes go
    to a shed column past the row (key_pos: (B, S_logical))."""
    B, s_log = key_pos.shape
    col = jnp.where(ok, abs_pos % s_log, s_log)
    kp = jnp.pad(key_pos, ((0, 0), (0, 1)), constant_values=-1)
    kp = kp.at[jnp.arange(B)[:, None], col].set(
        jnp.where(ok, abs_pos, -1))
    return kp[:, :s_log]


def paged_kv_write(kv: PagedKVCache, ks, vs, start) -> PagedKVCache:
    """Write S_new entries per sequence at [start_b, start_b + S_new)
    through the block table (the paged analog of ``_bulk_write``/
    ``kv_write``).  ks/vs: (L, B, S_new, Hkv, hd).  A write run longer than
    one logical ring keeps only the tail (matching the dense ring), so
    scatter targets stay duplicate-free."""
    B, s_new = ks.shape[1], ks.shape[2]
    start = _per_batch(start, B)
    s_log = kv.max_len
    if s_new >= s_log:
        ks, vs = ks[:, :, -s_log:], vs[:, :, -s_log:]
        start = start + (s_new - s_log)
        s_new = s_log
    abs_pos = start[:, None] + jnp.arange(s_new, dtype=jnp.int32)[None, :]
    valid = jnp.ones(abs_pos.shape, bool)
    pool_k, pool_v, sk, sv, ok = _pool_scatter(
        kv.pool_k, kv.pool_v, kv.block_table, ks, vs, abs_pos, valid,
        scale_k=kv.scale_k, scale_v=kv.scale_v)
    return dataclasses.replace(
        kv, pool_k=pool_k, pool_v=pool_v, scale_k=sk, scale_v=sv,
        key_pos=_keypos_scatter(kv.key_pos, abs_pos, ok),
        pos=start + s_new)


def paged_kv_commit(kv: PagedKVCache, k_new, v_new, accept_nodes, n_accept,
                    max_depth) -> PagedKVCache:
    """Paged analog of ``kv_commit``: write each sequence's accepted tree
    path through its block table.  Writes past ``n_accept[b]`` (and any
    write a frozen row would make past its reservation) hit the trash page."""
    idx = jnp.arange(max_depth, dtype=jnp.int32)
    sel = jax.vmap(lambda kn, nd: jnp.take(kn, nd, axis=1),
                   in_axes=(1, 0), out_axes=1)
    sel_k = sel(k_new, accept_nodes)                 # (L, B, Dmax, Hkv, hd)
    sel_v = sel(v_new, accept_nodes)
    abs_pos = kv.pos[:, None] + idx[None, :]
    valid = idx[None, :] < n_accept[:, None]
    pool_k, pool_v, sk, sv, ok = _pool_scatter(
        kv.pool_k, kv.pool_v, kv.block_table, sel_k, sel_v, abs_pos, valid,
        scale_k=kv.scale_k, scale_v=kv.scale_v)
    return dataclasses.replace(
        kv, pool_k=pool_k, pool_v=pool_v, scale_k=sk, scale_v=sv,
        key_pos=_keypos_scatter(kv.key_pos, abs_pos, ok),
        pos=kv.pos + n_accept.astype(jnp.int32))


def gather_pages(pool_layer, block_table):
    """Materialize one layer's logical (B, S_logical, Hkv, hd) view through
    the block table (the ref-backend read path; the Pallas kernel instead
    DMAs pages via scalar-prefetch).  Unreserved entries read the trash
    page — their slots are key_pos == -1, so every mask rejects them."""
    P, ps = pool_layer.shape[0], pool_layer.shape[1]
    t = jnp.where(block_table < 0, P - 1, block_table)
    ck = jnp.take(pool_layer, t, axis=0)      # (B, max_pages, ps, Hkv, hd)
    B, maxp = block_table.shape
    return ck.reshape((B, maxp * ps) + pool_layer.shape[2:])


def gather_pages_dequant(pool_layer, scale_layer, block_table):
    """``gather_pages`` for an int8 pool: dequantize while materializing the
    logical (B, S_logical, Hkv, hd) float32 view.  ``scale_layer (P, Hkv)``
    is one layer's per-page scales; the ref-backend mirror of the fused
    dequant inside the Pallas page walk.  ``scale_layer=None`` falls back to
    the verbatim gather (float pool)."""
    if scale_layer is None:
        return gather_pages(pool_layer, block_table)
    P, ps = pool_layer.shape[0], pool_layer.shape[1]
    t = jnp.where(block_table < 0, P - 1, block_table)
    ck = jnp.take(pool_layer, t, axis=0).astype(jnp.float32)
    sc = jnp.take(scale_layer, t, axis=0)     # (B, max_pages, Hkv)
    ck = ck * sc[:, :, None, :, None]
    B, maxp = block_table.shape
    return ck.reshape((B, maxp * ps) + pool_layer.shape[2:])


def paginate_cache(cache: "Cache", tables, *, page_size, n_pages,
                   kv_dtype=None) -> "Cache":
    """Convert a freshly-prefilled DENSE cache into the paged layout.

    ``tables (B, max_pages)`` comes from the host-side allocator.  Runs
    inside the engines' fused prefill jit; the dense cache is a transient
    (sized to the prompt, not max_len).  Entries older than one logical
    ring (an over-long prompt on a small reservation) are dropped — the
    row then freezes at its first capacity check, same as the dense path.

    ``kv_dtype`` picks the POOL dtype (default: the dense cache's own) —
    ``jnp.int8`` quantizes the prompt KV on the way in, arming each
    destination page's scale from the prefill write.
    """
    kv = cache.kv
    if kv is None or isinstance(kv, PagedKVCache):
        return cache
    if kv.window:
        raise ValueError("paged KV supports full attention only (window=0)")
    L, B, S, Hkv, hd = kv.k.shape
    pool_dtype = kv.k.dtype if kv_dtype is None else jnp.dtype(kv_dtype)
    s_log = tables.shape[1] * page_size
    pool_k = jnp.zeros((L, n_pages + 1, page_size, Hkv, hd), pool_dtype)
    pool_v = jnp.zeros_like(pool_k)
    scale = (jnp.zeros((L, n_pages + 1, Hkv), jnp.float32)
             if pool_dtype == jnp.int8 else None)
    abs_pos = kv.key_pos                                     # (B, S)
    valid = (abs_pos >= 0) & (abs_pos >= kv.pos[:, None] - s_log)
    pool_k, pool_v, sk, sv, ok = _pool_scatter(
        pool_k, pool_v, tables, kv.k, kv.v, abs_pos, valid,
        scale_k=scale, scale_v=scale)
    key_pos = _keypos_scatter(jnp.full((B, s_log), -1, jnp.int32),
                              abs_pos, ok)
    return dataclasses.replace(cache, kv=PagedKVCache(
        pool_k=pool_k, pool_v=pool_v, block_table=tables,
        key_pos=key_pos, pos=kv.pos, scale_k=sk, scale_v=sv,
        page_size=page_size))


def _per_batch(start_pos, batch):
    """Broadcast a scalar start position to (B,) int32."""
    return jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (batch,))


def _ring_match(abs_pos, valid, size):
    """Per-slot source index for a masked ring write.

    abs_pos: (D,) absolute positions being written; valid: (D,) write mask.
    Returns (written (S,), src (S,)): slot s takes entry src[s] iff
    written[s].  Expressed as gather + where rather than scatter — XLA CPU
    lowers batched dynamic scatters to a serialized loop, which dominated
    the batched commit path (see engine_bench).  Duplicate slots (a write
    run longer than the ring) resolve to the LAST write, matching scatter
    semantics.
    """
    D = abs_pos.shape[0]
    slots = abs_pos % size
    match = (jnp.arange(size, dtype=jnp.int32)[:, None] == slots[None, :]) \
        & valid[None, :]                                 # (S, D)
    written = jnp.any(match, axis=1)
    src = (D - 1) - jnp.argmax(match[:, ::-1], axis=1).astype(jnp.int32)
    return written, src


def kv_write(cache_k, cache_v, key_pos, k_new, v_new, start_pos):
    """Write S_new entries per sequence at positions [start_b, start_b+S_new).

    cache_k/v: (B, S, Hkv, hd) — per-layer slices (inside scan).
    key_pos: (B, S); k_new/v_new: (B, S_new, Hkv, hd).
    start_pos: () or (B,) — per-sequence absolute start positions.
    Ring indexing per sequence: slot = pos % S.
    Returns updated (cache_k, cache_v, key_pos).
    """
    S = cache_k.shape[1]
    s_new = k_new.shape[1]
    start = _per_batch(start_pos, cache_k.shape[0])

    def one(ck, cv, kp, kn, vn, st):
        abs_pos = st + jnp.arange(s_new, dtype=jnp.int32)
        written, src = _ring_match(abs_pos, jnp.ones((s_new,), bool), S)
        m = written[:, None, None]
        return (jnp.where(m, kn[src].astype(ck.dtype), ck),
                jnp.where(m, vn[src].astype(cv.dtype), cv),
                jnp.where(written, abs_pos[src], kp))

    return jax.vmap(one)(cache_k, cache_v, key_pos, k_new, v_new, start)


def kv_commit(kv, k_new, v_new, accept_nodes, n_accept,
              max_depth):
    """Write each sequence's accepted tree path into its ring buffer (dense)
    or through its block table (paged).

    k_new/v_new: (L, B, W, Hkv, hd) uncommitted tree KVs;
    accept_nodes: (B, Dmax) node ids of the accepted chain (padded);
    n_accept: (B,) accepted tokens per sequence (1..Dmax).
    Writes are masked per sequence: slots beyond n_accept[b] keep their
    previous contents, and ``pos`` advances by n_accept[b].
    """
    if isinstance(kv, PagedKVCache):
        return paged_kv_commit(kv, k_new, v_new, accept_nodes, n_accept,
                               max_depth)
    size = kv.max_len
    idx = jnp.arange(max_depth, dtype=jnp.int32)

    def one(ck, cv, kp, kn, vn, nodes, n, p):
        # ck/cv: (L, S, Hkv, hd); kn/vn: (L, W, Hkv, hd); kp: (S,)
        abs_pos = p + idx
        written, src = _ring_match(abs_pos, idx < n, size)
        sel_k = jnp.take(kn, nodes, axis=1)              # (L, Dmax, Hkv, hd)
        sel_v = jnp.take(vn, nodes, axis=1)
        m = written[None, :, None, None]
        return (jnp.where(m, sel_k[:, src].astype(ck.dtype), ck),
                jnp.where(m, sel_v[:, src].astype(cv.dtype), cv),
                jnp.where(written, abs_pos[src], kp))

    k2, v2, kp2 = jax.vmap(one, in_axes=(1, 1, 0, 1, 1, 0, 0, 0),
                           out_axes=(1, 1, 0))(
        kv.k, kv.v, kv.key_pos, k_new, v_new,
        accept_nodes, n_accept, kv.pos)
    return KVCache(k=k2, v=v2, key_pos=kp2,
                   pos=kv.pos + n_accept.astype(jnp.int32), window=kv.window)


# --------------------------------------------------------------------------
# Per-row slot primitives (continuous batching, runtime/scheduler.py).
#
# A batched cache is a bank of B independent rows; the scheduler treats each
# row as a slot that sequences are admitted into and evicted from at chunk
# boundaries.  Every helper below maps a function over the batched leaves of
# a ``Cache`` with the leaf's batch-axis position made explicit (KV k/v and
# Mamba/cross arrays carry batch at axis 1, key_pos/pos/xLSTM leaves at
# axis 0), so row surgery never touches the other rows.
# --------------------------------------------------------------------------
def _row_map(fn, *caches: "Cache") -> "Cache":
    """Apply ``fn(batch_axis, *leaves)`` over the batched leaves of Cache(s).

    All caches must share one structure (same model family + shapes apart
    from the batch axis).  Returns a new Cache built from fn's outputs.
    """
    c = caches[0]

    def go(axis, get):
        return fn(axis, *(get(x) for x in caches))

    kv = mamba = xl = ck = cv = None
    if c.kv is not None:
        kv = KVCache(k=go(1, lambda x: x.kv.k), v=go(1, lambda x: x.kv.v),
                     key_pos=go(0, lambda x: x.kv.key_pos),
                     pos=go(0, lambda x: x.kv.pos), window=c.kv.window)
    if c.mamba is not None:
        mamba = MambaState(ssm=go(1, lambda x: x.mamba.ssm),
                           conv=go(1, lambda x: x.mamba.conv),
                           pos=go(0, lambda x: x.mamba.pos))
    if c.xlstm is not None:
        layers = jax.tree_util.tree_map(
            lambda *ls: fn(0, *ls), *(x.xlstm.layers for x in caches))
        xl = XLSTMState(layers=layers, pos=go(0, lambda x: x.xlstm.pos))
    if c.cross_k is not None:
        ck = go(1, lambda x: x.cross_k)
        cv = go(1, lambda x: x.cross_v)
    return Cache(kv=kv, mamba=mamba, xlstm=xl, cross_k=ck, cross_v=cv)


def _without_kv(cache: Cache) -> Cache:
    return dataclasses.replace(cache, kv=None)


def tile_rows(cache: Cache, batch: int) -> Cache:
    """Broadcast a batch-1 cache to ``batch`` identical rows (used once to
    bootstrap the scheduler's resident state from the first admission)."""
    return _row_map(lambda axis, a: jnp.repeat(a, batch, axis=axis), cache)


def blank_paged_rows(row: Cache, batch: int, *, page_size, n_pages,
                     max_len, kv_dtype=None) -> Cache:
    """Paged bootstrap of the scheduler's resident bank from the first B=1
    dense-prefilled admission: non-KV leaves are tiled (masked rows never
    read them), the KV field becomes an EMPTY shared pool — blank rows hold
    no reservation, so unlike the dense ``tile_rows`` bootstrap no slot
    memory is spent on rows that are still free.  ``kv_dtype`` picks the
    pool dtype (default: the prefill's own; ``int8`` = quantized pool)."""
    dkv = row.kv
    if dkv is None:                       # recurrent-only family (xLSTM)
        return tile_rows(row, batch)
    out = _row_map(lambda axis, a: jnp.repeat(a, batch, axis=axis),
                   _without_kv(row))
    L, _, _, Hkv, hd = dkv.k.shape
    return dataclasses.replace(out, kv=init_paged_kv_cache(
        L, batch, max_len, Hkv, hd, page_size=page_size, n_pages=n_pages,
        dtype=dkv.k.dtype if kv_dtype is None else kv_dtype))


def reset_rows(cache: Cache, rows) -> Cache:
    """Clear the rows where ``rows (B,)`` is True: ``key_pos`` -> -1 (every
    attention mask rejects the slot), ``pos`` -> 0, KV/recurrent state
    zeroed.  A freed row is inert until ``insert_rows`` installs a freshly
    prefilled sequence — reset guarantees no stale KV survives eviction, it
    does not produce a decodable initial state (e.g. xLSTM stabilizer
    offsets are re-established by the admission prefill).

    Paged KV: the row's ``block_table`` entries drop to -1 (its pool pages
    go back to the allocator host-side; their contents are unreachable once
    no table references them) and any write the dead row still issues from
    inside a chunk redirects to the trash page.  Quantized pools
    deliberately do NOT touch the freed pages' scales here: the dead row's
    table is STALE bookkeeping — the scheduler releases pages host-side at
    completion and batches row resets to the END of the boundary, so by
    reset time a "freed" page may already carry a new resident admitted
    earlier in the SAME boundary, and zeroing its just-armed scale would
    let the next decode write re-arm it from the wrong amax (silent dequant
    corruption of the resident's already-quantized prompt).  A freed page's
    stale armed scale is unreachable garbage, exactly like its int8
    payload; ``_paged_insert_row`` un-arms the reservation at the only
    sound point — reserve time, zero-then-arm."""
    rows = jnp.asarray(rows, bool)

    def f(axis, a):
        shape = [1] * a.ndim
        shape[axis] = rows.shape[0]
        return jnp.where(rows.reshape(shape), jnp.zeros_like(a), a)

    if isinstance(cache.kv, PagedKVCache):
        kv = cache.kv
        out = _row_map(f, _without_kv(cache))
        return dataclasses.replace(out, kv=dataclasses.replace(
            kv,
            block_table=jnp.where(rows[:, None], jnp.int32(-1),
                                  kv.block_table),
            key_pos=jnp.where(rows[:, None], jnp.int32(-1), kv.key_pos),
            pos=jnp.where(rows, jnp.int32(0), kv.pos)))

    out = _row_map(f, cache)
    if out.kv is not None:
        out.kv.key_pos = jnp.where(rows[:, None], jnp.int32(-1),
                                   cache.kv.key_pos)
    return out


def insert_rows(cache: Cache, row, src: Cache, *, pages=None) -> Cache:
    """Copy row 0 of a batch-1 cache ``src`` into row ``row`` of ``cache``
    (admission: the new request's B=1 prefilled state takes over the slot).
    ``row`` may be a traced scalar, so one jitted insert serves every slot.

    When ``cache`` is paged, ``src`` is still DENSE (admission prefills at
    B=1 in the dense layout) and ``pages (max_pages,)`` — the row's fresh
    reservation, -1-padded — must be supplied; the prompt KV is scattered
    through it into the shared pool."""
    row = jnp.asarray(row, jnp.int32)

    def f(axis, big, small):
        upd = jax.lax.index_in_dim(small, 0, axis, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            big, upd.astype(big.dtype), row, axis)

    if isinstance(cache.kv, PagedKVCache):
        if pages is None:
            raise ValueError("paged insert_rows needs the row's pages")
        out = _row_map(f, _without_kv(cache), _without_kv(src))
        return dataclasses.replace(
            out, kv=_paged_insert_row(cache.kv, row, src.kv, pages))
    return _row_map(f, cache, src)


def _paged_insert_row(kv: PagedKVCache, row, dkv: KVCache, pages
                      ) -> PagedKVCache:
    """Scatter a dense B=1 prefill into ``row``'s fresh page reservation.

    Quantized pools un-arm the fresh reservation's scales FIRST, so the
    prompt write re-arms them from the new resident's own amax.  This is
    the ONLY place recycled-page scales are cleared: an evicted page keeps
    its stale armed scale until re-reserved (``reset_rows`` must not touch
    pool scales — its view of the dead row's pages is stale by the time
    the scheduler batches the reset; see its docstring)."""
    pages = jnp.asarray(pages, jnp.int32)
    s_log = kv.max_len
    abs_pos = dkv.key_pos[0]                              # (S_dense,)
    valid = (abs_pos >= 0) & (abs_pos >= dkv.pos[0] - s_log)
    sk, sv = kv.scale_k, kv.scale_v
    if sk is not None:
        sk = _zero_page_scales(sk, pages, jnp.ones(pages.shape, bool))
        sv = _zero_page_scales(sv, pages, jnp.ones(pages.shape, bool))
    pool_k, pool_v, sk, sv, ok = _pool_scatter(
        kv.pool_k, kv.pool_v, pages[None, :], dkv.k, dkv.v,
        abs_pos[None, :], valid[None, :], scale_k=sk, scale_v=sv)
    kp_row = _keypos_scatter(jnp.full((1, s_log), -1, jnp.int32),
                             abs_pos[None, :], ok)[0]
    return dataclasses.replace(
        kv, pool_k=pool_k, pool_v=pool_v, scale_k=sk, scale_v=sv,
        block_table=kv.block_table.at[row].set(pages),
        key_pos=kv.key_pos.at[row].set(kp_row),
        pos=kv.pos.at[row].set(dkv.pos[0]))


def slice_row(cache: Cache, row) -> Cache:
    """B=1 view of one bank row (the attention context a chunked-prefill
    piece extends).  ``row`` may be a traced scalar.  KV-only caches: the
    chunked-prefill path is gated to attention families, so recurrent /
    cross state never reaches here.

    Paged caches share the pool by reference — only the row's table,
    ``key_pos`` and ``pos`` are sliced, so the view costs O(max_pages), not
    a pool copy."""
    if cache.mamba is not None or cache.xlstm is not None \
            or cache.cross_k is not None:
        raise ValueError("slice_row supports KV-only caches "
                         "(chunked prefill is attention-family only)")
    row = jnp.asarray(row, jnp.int32)
    kv = cache.kv

    def rows(a, axis):
        return jax.lax.dynamic_slice_in_dim(a, row, 1, axis)

    if isinstance(kv, PagedKVCache):
        return Cache(kv=dataclasses.replace(
            kv, block_table=rows(kv.block_table, 0),
            key_pos=rows(kv.key_pos, 0), pos=rows(kv.pos, 0)))
    return Cache(kv=KVCache(k=rows(kv.k, 1), v=rows(kv.v, 1),
                            key_pos=rows(kv.key_pos, 0),
                            pos=rows(kv.pos, 0), window=kv.window))


def write_row_at(cache: Cache, row, ks, vs, start, n_valid) -> Cache:
    """Partial-row insert at an offset (chunked prefill): write the first
    ``n_valid`` of ``ks/vs (L, W, Hkv, hd)`` into row ``row`` at absolute
    positions [start, start + n_valid) and advance only that row's ``pos``.

    The complement of ``insert_rows`` (which replaces a whole row): pieces
    of one prompt land incrementally — dense rows via a masked ring scatter
    on the row, paged rows via ``_pool_scatter`` through the row's block
    table (each piece is paginated as it arrives; entries past ``n_valid``
    — tail-piece padding — are dropped, paged ones into the trash page).
    Requires W <= the row's logical length (piece slots must not alias).
    KV-only caches, same gate as ``slice_row``."""
    if cache.mamba is not None or cache.xlstm is not None \
            or cache.cross_k is not None:
        raise ValueError("write_row_at supports KV-only caches "
                         "(chunked prefill is attention-family only)")
    row = jnp.asarray(row, jnp.int32)
    kv = cache.kv
    W = ks.shape[1]
    idx = jnp.arange(W, dtype=jnp.int32)
    valid = idx < n_valid
    abs_pos = jnp.asarray(start, jnp.int32) + idx
    new_pos = kv.pos.at[row].set(abs_pos[0] + n_valid)

    if isinstance(kv, PagedKVCache):
        table_row = jax.lax.dynamic_slice_in_dim(kv.block_table, row, 1, 0)
        pool_k, pool_v, sk, sv, ok = _pool_scatter(
            kv.pool_k, kv.pool_v, table_row, ks[:, None], vs[:, None],
            abs_pos[None, :], valid[None, :],
            scale_k=kv.scale_k, scale_v=kv.scale_v)
        kp_row = _keypos_scatter(
            jax.lax.dynamic_slice_in_dim(kv.key_pos, row, 1, 0),
            abs_pos[None, :], ok)
        return dataclasses.replace(cache, kv=dataclasses.replace(
            kv, pool_k=pool_k, pool_v=pool_v, scale_k=sk, scale_v=sv,
            key_pos=jax.lax.dynamic_update_slice_in_dim(
                kv.key_pos, kp_row, row, 0),
            pos=new_pos))

    S = kv.max_len
    slots = abs_pos % S
    # masked scatter: invalid (padding) entries re-write the slot's current
    # contents — a gather of W slots, cheap next to the piece itself
    k_cur = kv.k[:, row, slots]
    v_cur = kv.v[:, row, slots]
    m = valid[:, None, None]
    return dataclasses.replace(cache, kv=dataclasses.replace(
        kv,
        k=kv.k.at[:, row, slots].set(
            jnp.where(m, ks.astype(kv.k.dtype), k_cur)),
        v=kv.v.at[:, row, slots].set(
            jnp.where(m, vs.astype(kv.v.dtype), v_cur)),
        key_pos=kv.key_pos.at[row, slots].set(
            jnp.where(valid, abs_pos, kv.key_pos[row, slots])),
        pos=new_pos))


_UNBOUNDED = 1 << 30


def capacity_left(cache: Cache) -> jax.Array:
    """(B,) decode slots left before a full (window=0) KV ring would wrap
    past capacity and silently overwrite its oldest entries.

    Sliding-window caches wrap by design and recurrent state is O(1) in
    context, so those report an effectively unbounded budget.  The chunk
    drivers fold this into the scan done-mask: a sequence freezes (stops
    emitting/committing) instead of corrupting its own attention.

    Paged caches count slots inside the row's page RESERVATION — a
    partially-reserved row (pool was short at admission) freezes when its
    last reserved page fills, exactly like a dense row hitting ``max_len``;
    the trash-page redirect below it is defense in depth, not the contract."""
    pos = cache.pos
    kv = cache.kv
    if isinstance(kv, PagedKVCache):
        n_alloc = jnp.sum(kv.block_table >= 0, axis=1).astype(jnp.int32)
        return n_alloc * jnp.int32(kv.page_size) - kv.pos
    if kv is None or kv.window:
        return jnp.full(pos.shape, _UNBOUNDED, jnp.int32)
    return jnp.int32(kv.max_len) - kv.pos


def in_window(ok, key_pos, q_pos, window):
    """``ok`` narrowed to keys inside the window (``key_pos > q_pos -
    window``).  ``window`` is a Python int, 0 = full attention (``ok``
    comes back as it was), or a traced per-layer scalar of a stack with
    ``layer_types`` (0 on its full-attention layers)."""
    if isinstance(window, jax.Array):
        return ok & ((window <= 0) | (key_pos > q_pos - window))
    if window:
        return ok & (key_pos > q_pos - window)
    return ok


def decode_mask(key_pos, q_pos, window):
    """Validity mask (T,) for one query at absolute position q_pos.

    key_pos: (T,) absolute positions in one sequence's cache (-1 empty).
    """
    ok = (key_pos >= 0) & (key_pos <= q_pos)
    return in_window(ok, key_pos, q_pos, window)


def batched_decode_mask(key_pos, q_pos, window):
    """Per-batch validity mask (B, W, S).

    key_pos: (B, S) absolute positions per slot; q_pos: (B, W) absolute query
    positions (they differ per sequence once acceptance lengths diverge).
    """
    kp = key_pos[:, None, :]                             # (B, 1, S)
    qp = q_pos[:, :, None]                               # (B, W, 1)
    ok = (kp >= 0) & (kp <= qp)
    return in_window(ok, kp, qp, window)


def prefill_mask(seq_len, window, q_offset=0, dtype=bool):
    """Causal (optionally windowed) (S, S) mask for prefill."""
    q = jnp.arange(seq_len)[:, None] + q_offset
    k = jnp.arange(seq_len)[None, :] + q_offset
    return in_window(k <= q, k, q, window)
