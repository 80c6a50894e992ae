"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode, which the other kernel tests run in, checks neither the
TPU's block-tiling rule nor its fast-memory budget; the chip's compiler,
installed here, checks both.  Each test compiles one kernel entry point
for one chip of a described ``v5e:2x2`` topology, at the head shapes of
Qwen2-0.5B (Hkv=2, hd=64) and of Vicuna-7B (Hkv=32, hd=128), and asserts
that the program holds a Mosaic kernel.  The Medusa draft is compiled at
Qwen2-0.5B's vocabulary to check that no sort spans a whole row; the
expert layer's grouped matmul and the windowed paged verify at
Mellum2-12B-A2.5B's widths.  Nothing runs on a chip.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.speculative import medusa as M
from repro.kernels import sparse_tree as KS
from repro.kernels import tree_attention as KT

HEADS = {"qwen2-0.5b": (14, 2, 64), "vicuna-7b": (32, 32, 128)}
B, S, PS, MAXP, N_PAGES = 4, 1024, 16, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with the persistent compilation cache
    off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                      # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _operands(sharding, model, W, kv_dtype):
    Hq, Hkv, hd = HEADS[model]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bf, i32 = jnp.bfloat16, jnp.int32
    pool = s((N_PAGES + 1, PS, Hkv, hd), kv_dtype)
    scale = s((N_PAGES + 1, Hkv), jnp.float32)
    return dict(
        q=s((B, W, Hq, hd), bf), kn=s((B, W, Hkv, hd), bf),
        ck=s((B, S, Hkv, hd), bf), key_pos=s((B, S), i32),
        q_pos=s((B, W), i32), mask=s((W, W), jnp.bool_), pool=pool,
        scale=scale, table=s((B, MAXP), i32),
        page_pos=s((B, MAXP * PS), i32))


@pytest.mark.parametrize("W", [1, 8])
@pytest.mark.parametrize("model", sorted(HEADS))
@pytest.mark.parametrize("entry", ["tree_attention", "sparse_tree_attention",
                                   "sparse_tree_attention_partial"])
def test_dense_kernels_compile_for_v5e(one_chip, entry, model, W):
    o = _operands(one_chip, model, W, jnp.bfloat16)
    if entry == "tree_attention":
        _compile(lambda *a: KT.tree_attention(*a, interpret=False),
                 o["q"], o["ck"], o["ck"], o["kn"], o["kn"], o["key_pos"],
                 o["q_pos"], o["q_pos"], o["mask"])
    else:
        kernel = getattr(KS, entry)
        _compile(lambda *a: kernel(*a, interpret=False),
                 o["q"], o["kn"], o["kn"], o["mask"])


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("W", [1, 8])
@pytest.mark.parametrize("model", sorted(HEADS))
@pytest.mark.parametrize("entry", ["paged_tree_attention",
                                   "paged_cache_attention"])
def test_paged_kernels_compile_for_v5e(one_chip, entry, model, W, kv_dtype):
    o = _operands(one_chip, model, W, kv_dtype)
    walk = (o["table"], o["page_pos"], o["q_pos"], o["q_pos"])
    if entry == "paged_tree_attention":
        _compile(lambda *a: KT.paged_tree_attention(*a, interpret=False),
                 o["q"], o["pool"], o["pool"], o["scale"], o["scale"],
                 o["kn"], o["kn"], o["table"], o["page_pos"], o["q_pos"],
                 o["q_pos"], o["mask"])
    else:
        _compile(lambda *a: KT.paged_cache_attention(*a, interpret=False),
                 o["q"], o["pool"], o["pool"], o["scale"], o["scale"], *walk)


def test_draft_compiles_without_a_full_vocabulary_sort(one_chip):
    """A wide ``lax.top_k`` compiles to a sort of the whole row on the chip;
    the draft's blocked top-k sorts 1,187 block maxima, the 10 chosen block
    ids and their 1,280 candidates, never the 151,936 columns."""
    cfg = get_config("qwen2-0.5b")
    heads = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: M.init_medusa(cfg, jax.random.PRNGKey(0))))
    hidden = jax.ShapeDtypeStruct((1, cfg.d_model), jnp.dtype(cfg.dtype),
                                  sharding=one_chip)
    text = jax.jit(functools.partial(
        M.draft_candidates, cfg, top_k=cfg.medusa_top_k)).lower(
            heads, hidden).compile().as_text()
    widths = {int(w) for line in text.splitlines() if " sort(" in line
              for w in re.findall(r"\[1,4,(\d+)\]", line.split(" sort(")[0])}
    assert sorted(widths) == [10, 1_187, 1_280]


@pytest.mark.parametrize("rows", [128, 1024], ids=["verify", "piece"])
@pytest.mark.parametrize("kn", [(2304, 896), (896, 2304)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch, rows, kn):
    """The expert layer's grouped matmul at Mellum2's widths (64 experts of
    896 over d_model 2,304, 8 layers stacked): a 16-token tree's 128 picks
    and a 128-token piece's 1,024, each m-tile visiting a whole expert
    matrix read from the stack in place."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_expert_matmul_impl", lambda: "pallas")
    k, n = kn

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(ops.grouped_matmul, s((rows, k), jnp.bfloat16),
             s((8, 64, k, n), jnp.bfloat16), s((64,), jnp.int32),
             s((), jnp.int32))


def test_paged_verify_with_a_layer_window_compiles_for_v5e(one_chip,
                                                          monkeypatch):
    """The paged tree verify masked by a traced per-layer window, at
    Mellum2's heads (32 query, 4 KV, 128 wide)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    W, Hq, Hkv, hd = 16, 32, 4, 128
    pool = s((N_PAGES + 1, PS, Hkv, hd), jnp.bfloat16)
    _compile(lambda q, pk, pv, kn, vn, t, kp, pos, d, m, win:
             ops.paged_tree_attention(q, pk, pv, kn, vn, t, kp, pos, d, m,
                                      window=win),
             s((1, W, Hq, hd), jnp.bfloat16), pool, pool,
             s((1, W, Hkv, hd), jnp.bfloat16), s((1, W, Hkv, hd),
                                                 jnp.bfloat16),
             s((1, MAXP), jnp.int32), s((1, MAXP * PS), jnp.int32),
             s((1,), jnp.int32), s((W,), jnp.int32),
             s((W, W), jnp.bool_), s((), jnp.int32))


def test_grouped_matmul_reads_a_layer_scan_stack_in_place(one_chip,
                                                        monkeypatch):
    """Inside a layer scan the grouped matmul reads the layer's experts
    from the whole (L, E, k, n) stack: no (E, k, n) slice of it is made
    (a scanned slice would copy every expert of the layer each step)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_expert_matmul_impl", lambda: "pallas")
    L, E, k, n = 4, 8, 256, 128

    def stack_scan(x, w, sizes):
        def body(h, layer):
            return ops.grouped_matmul(h, w, sizes, layer)[:, :k], None
        return jax.lax.scan(body, x, jnp.arange(L))[0]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = s((L, E, k, k), jnp.bfloat16)
    text = jax.jit(stack_scan).lower(s((128, k), jnp.bfloat16), w,
                                     s((E,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(rf"= bf16\[{E},{k},{k}\]", text)
