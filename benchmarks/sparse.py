"""Fig. 10b analogue: strategies for the tree-sparse attention component.

The paper compares (ARM CPU): naive COO sparse vs optimized COO SpMM vs
dense-with-mask.  On TPU the comparison becomes (DESIGN.md §2):

  dense-with-mask  — attend the W tree tokens against (cache + tree) as one
                     dense masked matmul (what cloud systems do),
  block-masked     — our Pallas sparse_tree kernel: tree part computed as a
                     VMEM-resident WxW masked block, dense part untouched,
  naive            — per-element gather/FMA oracle (the scalar-COO port that
                     does NOT fit the MXU; here to show WHY it non-transfers).

We report FLOPs + bytes (structural, hardware-independent) and CPU
wall-clock of the jitted forms (labelled: CPU time is NOT a TPU prediction).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.speculative import tree as T
from repro.kernels import ops as kops
from repro.kernels.ref import sparse_tree_ref
from repro.models import common as cm


def _naive_coo(q, k, v, mask):
    """Scalar-style COO reference: loop over nonzeros via masked gather —
    deliberately non-vectorized math (einsum-free inner ops)."""
    W = q.shape[1]
    scale = q.shape[-1] ** -0.5
    rows, cols = np.nonzero(np.asarray(mask))
    out_s = jnp.full(q.shape[:1] + (q.shape[2], W, W), -1e30, jnp.float32)
    qf = jnp.swapaxes(q.astype(jnp.float32), 1, 2)      # (B,H,W,hd)
    kf = jnp.swapaxes(k.astype(jnp.float32), 1, 2)
    g = q.shape[2] // k.shape[2]
    for r, c in zip(rows.tolist(), cols.tolist()):
        s = jnp.sum(qf[:, :, r] * jnp.repeat(kf, g, 1)[:, :, c], -1) * scale
        out_s = out_s.at[:, :, r, c].set(s)
    p = jax.nn.softmax(out_s, -1)
    vf = jnp.repeat(jnp.swapaxes(v.astype(jnp.float32), 1, 2), g, 1)
    o = jnp.einsum("bhrc,bhcd->bhrd", p, vf)
    return jnp.swapaxes(o, 1, 2)


def _time(f, *args, reps=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps


def run(width=64, ctx=256, H=32, Hkv=8, hd=128) -> list:
    accs = T.default_accs(5, 10)
    spec = T.build_tree(accs, width)
    mask = jnp.asarray(spec.mask)
    nnz = int(spec.mask.sum())
    B = 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, width, H, hd), jnp.float32)
    kn = jax.random.normal(ks[1], (B, width, Hkv, hd), jnp.float32)
    vn = jax.random.normal(ks[2], (B, width, Hkv, hd), jnp.float32)

    # structural terms
    dense_flops = 2 * 2 * width * (ctx + width) * H * hd
    block_flops = 2 * 2 * width * width * H * hd      # block-masked tree part
    coo_flops = 2 * 2 * nnz * H * hd                  # true nnz work
    print(f"# W={width} nnz={nnz}/{width*width} "
          f"dense-with-mask(ctx+tree)={dense_flops/1e6:.1f}MF "
          f"block-masked={block_flops/1e6:.1f}MF true-sparse={coo_flops/1e6:.1f}MF")

    t_block = _time(lambda: kops.sparse_tree_attention(q, kn, vn, mask))
    t_densemask = _time(lambda: jax.jit(sparse_tree_ref)(q, kn, vn,
                                                         jnp.ones_like(mask) & mask))
    t_naive = _time(lambda: _naive_coo(q, kn, vn, mask), reps=1)
    print(f"# CPU wall (NOT a TPU prediction): block={t_block*1e3:.2f}ms "
          f"dense-masked={t_densemask*1e3:.2f}ms naive-coo={t_naive*1e3:.1f}ms")
    print(f"# naive/block = {t_naive/t_block:.2f}x (paper: optimized sparse "
          f"3.49x over naive); tree-part FLOP saving vs dense-over-everything "
          f"= {dense_flops/block_flops:.2f}x")
    return [("fig10b_block_kernel_ms", t_block * 1e3, "cpu-interpret"),
            ("fig10b_naive_over_block", t_naive / t_block, "paper=3.49"),
            ("fig10b_flops_saving", dense_flops / block_flops,
             f"nnz={nnz}")] + run_int8(width=width, mask=mask, q=q,
                                       kn=kn, vn=vn, ctx=ctx, Hkv=Hkv,
                                       hd=hd)


def run_int8(*, width, mask, q, kn, vn, ctx, Hkv, hd) -> list:
    """int8 arm of the verify-path comparison: the fused fp32 paged walk
    vs the fused int8 (dequant-in-kernel) walk vs the split int8 page walk
    + block-masked tree kernel (``tree_kernel=sparse``).

    Cache-side BYTES are the structural story (an edge decode step is
    bandwidth-bound on the KV read, paper §II): int8 pages move 4x fewer
    pool bytes per step; wall-clock is CPU interpret-mode, labelled as
    such.  Parity is asserted against the fp32 fused walk inside the
    run (max|Δ| must sit under the documented quantization bound)."""
    from repro.runtime.cache import init_kv_cache, page_bytes, paginate_cache
    from repro.runtime.cache import Cache as _Cache
    B = q.shape[0]
    ps = 16
    n_pages = (ctx + ps - 1) // ps
    # one resident sequence of ctx tokens, paginated at both pool dtypes
    k_ctx = jax.random.normal(jax.random.PRNGKey(9), (1, B, ctx, Hkv, hd),
                              jnp.float32)
    v_ctx = jax.random.normal(jax.random.PRNGKey(10), (1, B, ctx, Hkv, hd),
                              jnp.float32)
    dense = init_kv_cache(1, B, ctx, Hkv, hd)
    dense = type(dense)(k=k_ctx, v=v_ctx,
                        key_pos=jnp.broadcast_to(jnp.arange(ctx), (B, ctx)),
                        pos=jnp.full((B,), ctx, jnp.int32), window=0)
    tables = jnp.broadcast_to(jnp.arange(n_pages, dtype=jnp.int32),
                              (B, n_pages))
    paged32 = paginate_cache(_Cache(kv=dense), tables, page_size=ps,
                             n_pages=n_pages).kv
    paged8 = paginate_cache(_Cache(kv=dense), tables, page_size=ps,
                            n_pages=n_pages, kv_dtype=jnp.int8).kv
    depth = jnp.zeros((width,), jnp.int32)     # flat tree at pos=ctx

    def fused(kv):
        return kops.paged_tree_attention(
            q, kv.pool_k[0], kv.pool_v[0], kn, vn, kv.block_table,
            kv.key_pos, kv.pos, depth, mask,
            scale_k=None if kv.scale_k is None else kv.scale_k[0],
            scale_v=None if kv.scale_v is None else kv.scale_v[0])

    def split(kv):
        cache_part = kops.paged_cache_attention(
            q, kv.pool_k[0], kv.pool_v[0], kv.block_table, kv.key_pos,
            kv.pos, depth, scale_k=kv.scale_k[0], scale_v=kv.scale_v[0])
        tree_part = kops.sparse_tree_attention_partial(q, kn, vn, mask)
        return cm.merge_partials([cache_part, tree_part])

    o32 = fused(paged32)
    o8 = fused(paged8)
    o8s = split(paged8)
    err_fused = float(jnp.max(jnp.abs(o8 - o32)))
    err_split = float(jnp.max(jnp.abs(o8s - o32)))
    assert err_fused < 3e-2 and err_split < 3e-2, (err_fused, err_split)

    t32 = _time(lambda: fused(paged32))
    t8 = _time(lambda: fused(paged8))
    t8s = _time(lambda: split(paged8))
    by32 = n_pages * page_bytes(1, ps, Hkv, hd, jnp.float32)
    by8 = n_pages * page_bytes(1, ps, Hkv, hd, jnp.int8)
    print(f"# int8 verify arm (ctx={ctx}, W={width}): cache bytes/step "
          f"fp32={by32} int8={by8} ({by32/by8:.2f}x fewer); max|err| "
          f"fused={err_fused:.2e} split={err_split:.2e}")
    print(f"# CPU wall (NOT a TPU prediction): fused-fp32={t32*1e3:.2f}ms "
          f"fused-int8={t8*1e3:.2f}ms split-int8={t8s*1e3:.2f}ms")
    return [("int8_cache_bytes_reduction", by32 / by8, f"ctx={ctx}"),
            ("int8_fused_err_vs_fp32", err_fused, "bound 3e-2"),
            ("int8_split_err_vs_fp32", err_split, "tree_kernel=sparse"),
            ("int8_fused_walk_ms", t8 * 1e3, "cpu-interpret"),
            ("int8_split_walk_ms", t8s * 1e3, "cpu-interpret")]


if __name__ == "__main__":
    run()
