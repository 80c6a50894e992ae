"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode, which the other kernel tests run in, checks neither the
TPU's block-tiling rule nor its fast-memory budget; the chip's compiler,
installed here, checks both.  Each test compiles one kernel entry point
for one chip of a described ``v5e:2x2`` topology, at the head shapes of
Qwen2-0.5B (Hkv=2, hd=64) and of Vicuna-7B (Hkv=32, hd=128), and asserts
that the program holds a Mosaic kernel.  The Medusa draft is compiled at
Qwen2-0.5B's vocabulary to check that no sort spans a whole row.  Nothing
runs on a chip.

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
