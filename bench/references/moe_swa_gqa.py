"""Plain reference of a sparse-expert decoder whose layers mix windowed
and full grouped-query attention (the Mellum 2 block): RMSNorm, rotary
positions (rotate-half form) with a table per attention kind, causal
softmax attention limited to a window on the sliding layers, a router
(softmax over every expert in float32, top-k, the k gates renormalized)
over SwiGLU experts with no shared expert, a final RMSNorm and an untied
output head.

It imports nothing of the program: straightforward ``jax.numpy`` over the
benchmark's weights, one whole prompt-plus-served-tokens sequence at a
time, layer by layer under ``lax.scan`` so one layer's float32 copy of
the attention weights is alive at a time.  Attention runs in blocks of
``Q_BLOCK`` queries against every key, so a long sequence's score matrix
never exists whole; the experts run one at a time over every token,
each token weighted by its gate where it picked the expert and by 0
where it did not.  Sequences are padded at the end to a fixed length, so
one compiled program serves every request of a cell.

Rotary tables: sliding layers use the plain table at ``rope_theta``; full
layers YaRN's (transformers' ``_compute_yarn_parameters``, truncated):
with ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln theta)``, ``low =
floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, frequency i
is ``theta_i / factor * ramp_i + theta_i * (1 - ramp_i)``, ``ramp_i =
clip((i - low) / (high - low), 0, 1)``, and cos and sin are multiplied by
``attention_factor``.

``logits(..., mode="f32")`` is the reference: float32 at the highest
matmul precision.  ``mode="int8"`` is the control, one precision below the
configuration's bfloat16: every projection, expert and the output head in
int8 (weights per output channel, activations per token, symmetric, int32
accumulation).  The router stays float32 in both, as the configuration
states it.

Beside the forward pass it describes the architecture to the benchmark:
``dims`` (its sizes and ``token_params``), ``program_kwargs`` (the
program's ``ModelConfig`` for it) and ``make_weights`` (its weights in
the program's layout).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import around_one, normal

HI = jax.lax.Precision.HIGHEST
# queries per attention block of the reference
Q_BLOCK = 128


def dims(config: dict) -> dict:
    """The sizes, from a configuration file in the source's own key names,
    and ``token_params``: the weights one token multiplies through, every
    layer's projections, its router and its ``num_experts_per_tok``
    experts, and the output head over the vocabulary."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    L = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][:L])
    rope = config["rope_parameters"]
    full = rope["full_attention"]
    out = {
        "d_model": d, "n_layers": L, "n_heads": H,
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", d // H),
        "d_expert": config["moe_intermediate_size"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "vocab": config["vocab_size"],
        "rms_eps": float(config["rms_norm_eps"]),
        "rope_theta": float(rope["sliding_attention"]["rope_theta"]),
        "window": int(config["sliding_window"]),
        "layer_types": kinds,
        "yarn_theta": float(full["rope_theta"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
    }
    if config.get("attention_bias") or config["tie_word_embeddings"]:
        raise ValueError("moe_swa_gqa: no attention bias, untied head")
    if any(t != "sparse" for t in config["mlp_layer_types"][:L]):
        raise ValueError("moe_swa_gqa: every MLP is sparse")
    hq, hkv, hd = H, out["n_kv_heads"], out["head_dim"]
    attn = d * hd * (2 * hq + 2 * hkv)
    router = d * out["n_experts"]
    expert = 3 * d * out["d_expert"]
    out["token_params"] = L * (attn + router + out["top_k"] * expert) \
        + d * out["vocab"]
    return out


def program_kwargs(config: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for this
    architecture; the harness adds name, source, serving settings and
    dtype."""
    d = dims(config)
    if not d["norm_topk"] or d["yarn_theta"] != d["rope_theta"]:
        raise ValueError("the program renormalizes the top-k gates and "
                         "shares one rope_theta between the kinds")
    return {"arch_type": "moe", "num_layers": d["n_layers"],
            "d_model": d["d_model"], "num_heads": d["n_heads"],
            "num_kv_heads": d["n_kv_heads"], "head_dim": d["head_dim"],
            "d_ff": d["d_expert"], "vocab_size": d["vocab"],
            "num_experts": d["n_experts"], "experts_per_token": d["top_k"],
            "rope_theta": d["rope_theta"], "rmsnorm_eps": d["rms_eps"],
            "tie_embeddings": False, "sliding_window": d["window"],
            "layer_types": d["layer_types"],
            "rope_yarn": {"factor": d["yarn_factor"],
                          "original_max_position": d["yarn_original"],
                          "beta_fast": d["yarn_beta_fast"],
                          "beta_slow": d["yarn_beta_slow"],
                          "attention_factor": d["yarn_attention_factor"]}}


def make_weights(dims: dict, keys) -> dict:
    """The model's parameters in the program's layout and ``dims["dtype"]``
    (the router in float32), each leaf drawn from the next key of the
    iterator ``keys``.  Projections and experts are N(0, 1/fan_in), the
    router N(0, 1/d_model); embeddings N(0, 0.02^2); norm scales near 1."""
    dt = jnp.dtype(dims["dtype"])
    d, L, f = dims["d_model"], dims["n_layers"], dims["d_expert"]
    E = dims["n_experts"]
    hd, hq, hkv = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    vp = dims["vocab_padded"]
    return {
        "embed": normal(next(keys), (vp, d), 0.02, dt),
        "layers": {
            "ln1": around_one(next(keys), (L, d), dt),
            "ln2": around_one(next(keys), (L, d), dt),
            "attn": {
                "wq": normal(next(keys), (L, d, hq * hd), d ** -0.5, dt),
                "wk": normal(next(keys), (L, d, hkv * hd), d ** -0.5, dt),
                "wv": normal(next(keys), (L, d, hkv * hd), d ** -0.5, dt),
                "wo": normal(next(keys), (L, hq * hd, d), (hq * hd) ** -0.5,
                             dt),
            },
            "moe": {
                "router": normal(next(keys), (L, d, E), d ** -0.5,
                                 jnp.float32),
                "w_gate": normal(next(keys), (L, E, d, f), d ** -0.5, dt),
                "w_up": normal(next(keys), (L, E, d, f), d ** -0.5, dt),
                "w_down": normal(next(keys), (L, E, f, d), f ** -0.5, dt),
            },
        },
        "ln_f": around_one(next(keys), (d,), dt),
        "lm_head": normal(next(keys), (d, vp), d ** -0.5, dt),
    }


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


def yarn_inv_freq(d: dict) -> np.ndarray:
    """YaRN's inverse frequencies of the full layers (float64), the
    closed form in the module's docstring."""
    hd, theta = d["head_dim"], d["yarn_theta"]
    i = np.arange(hd // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / hd)

    def corr(rot):
        return hd * math.log(d["yarn_original"] / (2 * math.pi * rot)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(d["yarn_beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return base / d["yarn_factor"] * ramp + base * (1.0 - ramp)


def _tables(d: dict):
    """Per layer: (inverse frequencies (L, hd/2), cos/sin scale (L,),
    window (L,), 0 = full)."""
    hd = d["head_dim"]
    plain = d["rope_theta"] ** (-2.0 * np.arange(hd // 2) / hd)
    yarn = yarn_inv_freq(d)
    inv, scale, win = [], [], []
    for kind in d["layer_types"]:
        full = kind == "full_attention"
        inv.append(yarn if full else plain)
        scale.append(d["yarn_attention_factor"] if full else 1.0)
        win.append(0 if full else d["window"])
    return (jnp.asarray(np.stack(inv), jnp.float32),
            jnp.asarray(scale, jnp.float32), jnp.asarray(win, jnp.int32))


def _rope(x, pos, inv, scale):
    """x: (T, H, hd); rotate-half rotary embedding at positions ``pos``."""
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (cos * scale) + rot * (sin * scale)


def _attend(q, k, v, win):
    """Causal attention of every query against every key, in blocks of
    ``Q_BLOCK`` queries; keys at or below ``query - win`` are masked where
    ``win > 0``.  q: (T, Hq, hd); k, v: (T, Hq, hd)."""
    T, H, hd = q.shape
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        ok = kpos[None, :] <= qpos[:, None]
        ok &= (win <= 0) | (kpos[None, :] > qpos[:, None] - win)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H * hd)


def _experts(h, moe, d, mm):
    """Routed SwiGLU experts over every token, one expert at a time."""
    logits = jnp.matmul(h, moe["router"].astype(jnp.float32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top, idx = jax.lax.top_k(probs, d["top_k"])
    if d["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # each token's gate for each expert: its renormalized probability where
    # the expert is among its top k, else 0
    gate = jnp.sum(jax.nn.one_hot(idx, d["n_experts"], dtype=jnp.float32)
                   * top[..., None], axis=1)                   # (T, E)

    def one(acc, e):
        wg, wu, wd, g = e
        y = mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (moe["w_gate"], moe["w_up"], moe["w_down"],
                           gate.T))
    return out


@partial(jax.jit, static_argnames=("dims", "n_out", "mode"))
def _forward(params, tokens, start, *, dims, n_out, mode):
    d = dict(dims)
    mm = _mm_int8 if mode == "int8" else _mm
    T = tokens.shape[0]
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    g = hq // hkv
    eps = d["rms_eps"]
    pos = jnp.arange(T, dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, xs):
        lp, inv, scale, win = xs
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"], eps)
        q = _rope(mm(h, a["wq"]).reshape(T, hq, hd), pos, inv, scale)
        k = _rope(mm(h, a["wk"]).reshape(T, hkv, hd), pos, inv, scale)
        v = mm(h, a["wv"]).reshape(T, hkv, hd)
        k = jnp.repeat(k, g, axis=1)             # query head h reads kv h // g
        v = jnp.repeat(v, g, axis=1)
        x = x + mm(_attend(q, k, v, win), a["wo"])
        h = _rmsnorm(x, lp["ln2"], eps)
        return x + _experts(h, lp["moe"], d, mm), None

    x, _ = jax.lax.scan(layer, x, (params["layers"],) + _tables(d))
    # logits only where a served token was predicted: rows start .. +n_out
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    rows = _rmsnorm(rows, params["ln_f"], eps)
    return mm(rows, params["lm_head"])[:, :d["vocab"]]


def logits(params, dims: dict, prompt, served, *, pad_to: int,
           mode: str = "f32"):
    """Logits (len(served), vocab) float32 of the positions that predicted
    each served token, for the sequence prompt + served[:-1] padded to
    ``pad_to`` tokens (a multiple of ``Q_BLOCK``)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n = len(served)
    n_out = int(dims["n_out_max"])
    seq = np.zeros((pad_to,), np.int32)
    body = np.concatenate([prompt, served[:-1]])
    if len(body) > pad_to or n > n_out or pad_to % Q_BLOCK:
        raise ValueError(f"sequence of {len(body)} tokens and {n} served "
                         f"exceeds the reference's {pad_to}/{n_out}, or "
                         f"{pad_to} is no multiple of {Q_BLOCK}")
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
