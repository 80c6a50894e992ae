"""Plain reference of a dense decoder with grouped-query attention (the
Llama/Qwen2/Mistral block): RMSNorm, rotary positions (rotate-half form),
causal softmax attention with optional q/k/v biases, SwiGLU MLP, a final
RMSNorm and the output head (tied to the embedding or not).

It imports nothing of the program: straightforward ``jax.numpy`` over the
benchmark's weights, one whole prompt-plus-served-tokens sequence at a
time, layer by layer under ``lax.scan`` so one layer's float32 copy of the
weights is alive at a time.  Sequences are padded at the end to a fixed
length, so one compiled program serves every request of a cell (causal
attention: the padding never reaches an earlier position).

``logits(..., mode="f32")`` is the reference: float32 at the highest
matmul precision.  ``mode="int8"`` is the control, the same reference
computed one precision below the configuration's bfloat16: every linear
layer in int8 (weights per output channel, activations per token,
symmetric, int32 accumulation).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _mm_int8(x, w):
    w = w.astype(jnp.float32)
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-12) / 127.0
    qw = jnp.clip(jnp.round(w / sw), -127, 127).astype(jnp.int8)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     1e-12) / 127.0
    qx = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


@partial(jax.jit, static_argnames=("dims", "n_out", "mode"))
def _forward(params, tokens, start, *, dims, n_out, mode):
    d = dict(dims)
    mm = _mm_int8 if mode == "int8" else _mm
    T = tokens.shape[0]
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    g = hq // hkv
    eps, theta = d["rms_eps"], d["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"], eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if "bq" in a:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            v = v + a["bv"].astype(jnp.float32)
        q = _rope(q.reshape(T, hq, hd), pos, theta)
        k = _rope(k.reshape(T, hkv, hd), pos, theta)
        v = v.reshape(T, hkv, hd)
        k = jnp.repeat(k, g, axis=1)             # query head h reads kv h // g
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, -1)
        x = x + mm(o, a["wo"])
        h = _rmsnorm(x, lp["ln2"], eps)
        m = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                   m["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    # logits only where a served token was predicted: rows start .. +n_out
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    rows = _rmsnorm(rows, params["ln_f"], eps)
    head = params["embed"].T if "lm_head" not in params \
        else params["lm_head"]
    return mm(rows, head)[:, :d["vocab"]]


def logits(params, dims: dict, prompt, served, *, pad_to: int,
           mode: str = "f32"):
    """Logits (len(served), vocab) float32 of the positions that predicted
    each served token, for the sequence prompt + served[:-1] padded to
    ``pad_to`` tokens."""
    import numpy as np

    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n = len(served)
    n_out = int(dims["n_out_max"])
    seq = np.zeros((pad_to,), np.int32)
    body = np.concatenate([prompt, served[:-1]])
    if len(body) > pad_to or n > n_out:
        raise ValueError(f"sequence of {len(body)} tokens and {n} served "
                         f"exceeds the reference's {pad_to}/{n_out}")
    seq[:len(body)] = body
    start = len(prompt) - 1
    # the padded tail must not run past the sequence: rows start..start+n_out
    start_c = min(start, pad_to - n_out)
    key = tuple(sorted((k, v) for k, v in dims.items()
                       if not isinstance(v, (list, dict))))
    out = _forward(params, jnp.asarray(seq), jnp.asarray(start_c, jnp.int32),
                   dims=key, n_out=n_out, mode=mode)
    off = start - start_c
    return np.asarray(out[off:off + n], np.float32)
