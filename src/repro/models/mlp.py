"""SwiGLU MLP and top-k MoE: a dropless sorted dispatch for serving and
GShard-style grouped one-hot dispatch for training.

Serving (``moe_dropless``) computes every pick: picks are sorted by
expert and run through a grouped matmul (``kernels/ops.grouped_matmul``)
that reads only the routed experts' weights, so a prefill piece, an
extension, a tree verify and a one-token decode give the same result for
a token whatever else is in the call.

Training (``moe_apply``) uses the capacity-factor one-hot einsum form
(GShard/MaxText style): it lowers to MXU-friendly einsums whose expert
dimension shards cleanly on the `model` mesh axis (all-to-all appears in
SPMD HLO).  Dropped tokens (over capacity) pass through on the residual
path — standard.

Both route alike (``route``): softmax over the experts in float32, top-k,
the k gates renormalized to sum to 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm


def mlp_init(cfg, rng):
    ks = jax.random.split(rng, 3)
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    return {
        "w_gate": cm.dense_init(ks[0], d, f, dt),
        "w_up": cm.dense_init(ks[1], d, f, dt),
        "w_down": cm.dense_init(ks[2], f, d, dt),
    }


def mlp_apply(cfg, p, x):
    return cm.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


# --------------------------------------------------------------------------
# MoE
# --------------------------------------------------------------------------
def moe_init(cfg, rng):
    ks = jax.random.split(rng, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = jnp.dtype(cfg.dtype)

    def einit(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    return {
        "router": cm.dense_init(ks[0], d, e, jnp.dtype(jnp.float32)),
        "w_gate": einit(ks[1], (e, d, f), d),
        "w_up": einit(ks[2], (e, d, f), d),
        "w_down": einit(ks[3], (e, f, d), f),
    }


def route(cfg, p, x):
    """x: (..., d) -> (probs (..., E), gates (..., K), experts (..., K)):
    softmax over the experts in float32 at full matmul precision, the top
    ``experts_per_token``, their gates renormalized to sum to 1."""
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return probs, gates, experts


# the experts' weights, stacked (E, ...) per layer
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_dropless(cfg, p, x, stacks):
    """x: (B, S, d) -> (out (B, S, d), counts int32 (2,)): every token's
    every pick computed (no capacity, no drop).

    The B*S*K picks are sorted by expert; one grouped matmul per weight
    (gate, up, down) runs each expert's rows against that expert's
    weights alone, so an expert no pick chose is never read.  ``p``
    holds the layer's router and its index ``p["layer"]`` into
    ``stacks``, every layer's ``(L, E, ...)`` expert weights, which are
    read in place (a scanned slice of them would copy every expert of the
    layer first).  ``counts`` = (picks, distinct experts touched), what
    the call read, for the serving counters
    (``runtime/telemetry.ExpertCounters``)."""
    from repro.kernels import ops as kops
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    layer = p["layer"]
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        _, gates, experts = route(cfg, p, xt)                  # (T, K)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)                 # by expert
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        rows = jnp.take(xt, order // K, axis=0)                # (T*K, d)
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(kops.grouped_matmul(rows, stacks["w_gate"], sizes,
                                            layer))
        h = h * kops.grouped_matmul(rows, stacks["w_up"], sizes, layer)
        y = kops.grouped_matmul(h, stacks["w_down"], sizes, layer)
    picks = jnp.zeros_like(y).at[order].set(y).reshape(B * S, K, d)
    out = jnp.einsum("tkd,tk->td", picks.astype(jnp.float32), gates)
    counts = jnp.stack([jnp.int32(flat.shape[0]),
                        jnp.sum(sizes > 0).astype(jnp.int32)])
    return out.astype(x.dtype).reshape(B, S, d), counts


def moe_apply(cfg, p, x, *, capacity_factor=1.25, group_size=256):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Tokens are split into groups of <=``group_size`` before the one-hot
    dispatch.  Grouping bounds the dispatch einsum's FLOPs and memory at
    O(g * E * cap) per group (cap = g*K/E*cf) instead of O(S^2)-scaling when
    the whole sequence is one group — the same reason GShard dispatches per
    group.  Groups align with the token sharding, so the expert einsum (whose
    E axis shards on `model`) carries the all-to-all.
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    g = min(group_size, T)
    while T % g:                                   # largest divisor <= group_size
        g -= 1
    G = T // g
    # small groups run with cap = g (nothing dropped); larger groups use
    # the standard capacity factor (dropped tokens ride the residual).
    # Serving never comes here: it routes dropless (``moe_dropless``).
    if g <= 32:
        cap = g
    else:
        cap = max(K, int(g * K / E * capacity_factor))
    xg = x.reshape(G, g, d)

    probs, gate_vals, gate_idx = route(cfg, p, xg)             # (G,g,E|K)

    # rank of each (token, k) choice inside its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # (G,g,K,E)
    flat = onehot.reshape(G, g * K, E)
    rank = (jnp.cumsum(flat, axis=1) - flat)                   # (G,g*K,E)
    rank = jnp.sum(rank * flat, axis=-1).reshape(G, g, K)
    keep = (rank < cap).astype(x.dtype)                        # capacity drop

    oh_e = jax.nn.one_hot(gate_idx, E, dtype=x.dtype) * keep[..., None]
    oh_c = jax.nn.one_hot(rank, cap, dtype=x.dtype)
    disp = jnp.einsum("gske,gskc->gsec", oh_e, oh_c)           # (G,g,E,cap)
    comb = jnp.einsum("gske,gskc,gsk->gsec", oh_e, oh_c,
                      gate_vals.astype(x.dtype))

    xe = jnp.einsum("gsec,gsd->gecd", disp, xg)                # (G,E,cap,d)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])          # (G,E,cap,d)
    out = jnp.einsum("gsec,gecd->gsd", comb, ye).reshape(B, S, d)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_coef
    return out, aux
