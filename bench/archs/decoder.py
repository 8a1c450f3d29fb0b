"""The decoder of the Qwen3 and OLMoE configurations, in plain ``jax.numpy``.

A pre-norm decoder: every layer is grouped-query attention (rotary
embedding, optional QK-norm) followed by a SwiGLU feed-forward or a top-k
routed mixture of SwiGLU experts with per-expert capacity; an untied head.
Written from the published descriptions; it imports nothing of the program
under test.  ``bench/reference.py`` finds it by the name ``decoder``, the
architecture of every configuration file without an ``"arch"`` key.

Memory: every layer and every attention head group is rematerialized and
experts run in blocks, so the reference fits on the cell's own chips at the
timed sizes.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8        # experts evaluated together in the dense MoE
#                         (the expert count is a multiple of it)
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class Arch:
    """The decoder, as the configuration file states it (as run)."""

    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    layers: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qk_norm: bool
    experts: int = 0
    top_k: int = 0
    norm_topk: bool = True
    capacity_factor: float = 0.0
    aux_weight: float = 0.0

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(d_model=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], layers=c["num_hidden_layers"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]), qk_norm=bool(c["qk_norm"]),
                   experts=c.get("num_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   norm_topk=bool(c.get("norm_topk_prob", True)),
                   capacity_factor=float(c.get("capacity_factor", 0.0)),
                   aux_weight=float(c.get("router_aux_loss_coef", 0.0)))


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, a: Arch):
    (k_slot,) = jax.random.split(key, 1)
    km, kf = jax.random.split(k_slot)
    kq, kk, kv, ko = jax.random.split(km, 4)
    d, hd = a.d_model, a.head_dim
    mixer = {
        "wq": _normal(kq, (d, a.heads * hd), 1 / math.sqrt(d)),
        "wk": _normal(kk, (d, a.kv_heads * hd), 1 / math.sqrt(d)),
        "wv": _normal(kv, (d, a.kv_heads * hd), 1 / math.sqrt(d)),
        "wo": _normal(ko, (a.heads * hd, d), 1 / math.sqrt(a.heads * hd)),
    }
    if a.qk_norm:
        mixer["q_norm"] = jnp.ones((hd,), jnp.float32)
        mixer["k_norm"] = jnp.ones((hd,), jnp.float32)
    if a.experts:
        kr, kg, ku, kd = jax.random.split(kf, 4)
        e = a.experts
        ffn = {"router": _normal(kr, (d, e), 1 / math.sqrt(d)),
               "w_gate": _normal(kg, (e, d, a.d_ff), 1 / math.sqrt(d)),
               "w_up": _normal(ku, (e, d, a.d_ff), 1 / math.sqrt(d)),
               "w_down": _normal(kd, (e, a.d_ff, d), 1 / math.sqrt(a.d_ff))}
    else:
        kg, ku, kd = jax.random.split(kf, 3)
        ffn = {"w_gate": _normal(kg, (d, a.d_ff), 1 / math.sqrt(d)),
               "w_up": _normal(ku, (d, a.d_ff), 1 / math.sqrt(d)),
               "w_down": _normal(kd, (a.d_ff, d), 1 / math.sqrt(a.d_ff))}
    return {"ffn": ffn, "mixer": mixer,
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32)}


def init_params(key, a: Arch):
    """Weights: N(0, 1/fan_in) matrices, N(0, 0.02²) embedding, unit norm
    scales; the key is split embed / blocks / head, and blocks per layer."""
    ke, kb, kh, _ = jax.random.split(key, 4)
    layers = [_layer(k, a) for k in jax.random.split(kb, a.layers)]
    blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {"blocks": {"slot0": blocks},
            "embed": _normal(ke, (a.vocab, a.d_model), 0.02),
            "final_norm": jnp.ones((a.d_model,), jnp.float32),
            "head": _normal(kh, (a.d_model, a.vocab), 1 / math.sqrt(a.d_model))}


def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, theta):
    """Rotary embedding, rotate-half form; x: (B, S, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _group_attention(q, k, v):
    """Causal softmax attention of one kv head's group of q heads.
    q: (B, S, G, hd); k, v: (B, S, hd)."""
    s = q.shape[1]
    scores = jnp.einsum("bqgd,bkd->bgqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bgqk,bkd->bqgd", probs, v)


def attention(p, z, a: Arch):
    b, s, _ = z.shape
    q = (z @ p["wq"]).reshape(b, s, a.heads, a.head_dim)
    k = (z @ p["wk"]).reshape(b, s, a.kv_heads, a.head_dim)
    v = (z @ p["wv"]).reshape(b, s, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"], a.rms_eps)
        k = rmsnorm(k, p["k_norm"], a.rms_eps)
    q, k = rope(q, a.rope_theta), rope(k, a.rope_theta)
    g = a.heads // a.kv_heads
    outs = [jax.checkpoint(_group_attention)(q[:, :, i * g:(i + 1) * g],
                                             k[:, :, i], v[:, :, i])
            for i in range(a.kv_heads)]
    out = jnp.concatenate(outs, axis=2).reshape(b, s, a.heads * a.head_dim)
    return out @ p["wo"]


def mlp(p, z):
    return (jax.nn.silu(z @ p["w_gate"]) * (z @ p["w_up"])) @ p["w_down"]


def _expert_block(x, wg, wu, wd, weights):
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg))
    h = h * jnp.einsum("td,edf->etf", x, wu)
    y = jnp.einsum("etf,efd->etd", h, wd)
    return jnp.einsum("te,etd->td", weights, y)


def moe(p, z, a: Arch):
    """Top-k routed experts with per-expert capacity.

    Gates are the softmax probabilities of the k chosen experts (rescaled
    to sum to one where ``norm_topk``).  Each expert takes at most
    C = max(8, min(ceil(T·k/E·cf), T)) of its (token, choice) pairs, in
    token order; the rest are dropped and pass through the residual.  The
    load-balance loss is E·Σ_e f_e·p_e.  Experts are evaluated densely on
    every token and weighted by the (mostly zero) combine matrix."""
    b, s, d = z.shape
    t, e, k = b * s, a.experts, a.top_k
    x = z.reshape(t, d)
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, k)
    if a.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat = chosen.reshape(-1)
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
    counts = onehot.sum(0)
    aux = e * jnp.sum(probs.mean(0) * counts / (t * k))
    cap = max(8, min(int(math.ceil(t * k / e * a.capacity_factor)), t))
    before = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(t * k), flat]
    keep = before < cap
    token = jnp.repeat(jnp.arange(t), k)
    combine = jnp.zeros((t, e), jnp.float32).at[token, flat].add(
        jnp.where(keep, gates.reshape(-1), 0.0)).astype(x.dtype)

    nb = e // EXPERT_BLOCK
    blocks = lambda w: w.reshape((nb, EXPERT_BLOCK) + w.shape[1:])

    def body(acc, blk):
        return acc + _expert_block(x, *blk), None

    out, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((t, d), x.dtype),
        (blocks(p["w_gate"]), blocks(p["w_up"]), blocks(p["w_down"]),
         combine.reshape(t, nb, EXPERT_BLOCK).transpose(1, 0, 2)))
    return out.reshape(b, s, d), aux


def _layer_forward(h, lp, a: Arch):
    h = h + attention(lp["mixer"], rmsnorm(h, lp["norm1"], a.rms_eps), a)
    z = rmsnorm(h, lp["norm2"], a.rms_eps)
    if a.experts:
        y, aux = moe(lp["ffn"], z, a)
    else:
        y, aux = mlp(lp["ffn"], z), jnp.zeros((), jnp.float32)
    return h + y, aux


def loss(params, tokens, labels, a: Arch, mask=None):
    """Mean next-token cross-entropy over the tokens ``mask`` keeps (all by
    default), plus the weighted load-balance loss.  Returns (total, lm_loss)."""
    h = params["embed"][tokens]
    aux_total = jnp.zeros((), jnp.float32)
    blocks = params["blocks"]["slot0"]
    for i in range(a.layers):
        lp = jax.tree_util.tree_map(lambda x: x[i], blocks)
        h, aux = jax.checkpoint(_layer_forward, static_argnums=2)(h, lp, a)
        aux_total = aux_total + aux
    h = rmsnorm(h, params["final_norm"], a.rms_eps)
    logits = (h @ params["head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = jnp.ones(labels.shape, jnp.float32) if mask is None else mask
    lm = jnp.sum((lse - picked) * mask) / jnp.sum(mask)
    return lm + a.aux_weight * aux_total, lm


def from_config(config: dict) -> Arch:
    return Arch.from_config(config)


# the program's ModelConfig fields each published key of a decoder's config
# file names, beyond the keys every configuration has (``cell.PROGRAM_FIELDS``)
PROGRAM_FIELDS = {
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "num_experts": "moe_num_experts",
    "num_experts_per_tok": "moe_top_k",
    "capacity_factor": "moe_capacity_factor",
    "router_aux_loss_coef": "moe_aux_weight",
}


# ---------------------------------------------------------------------------
# model FLOPs per trained token
# ---------------------------------------------------------------------------
#
# 6 × the matmul weights a token passes through (forward 2, backward 4): the
# attention projections, the feed-forward (for MoE the router and the top-k
# experts only) and the head; plus causal attention's two matmuls (QKᵀ and
# PV) over the causal half, the backward twice the forward.  Rematerialized
# recomputation and the embedding lookup do not count.


def matmul_weights_per_token(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    attn = d * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])
    if c.get("num_experts"):
        ffn = (d * c["num_experts"]
               + c["num_experts_per_tok"] * 3 * d * c["intermediate_size"])
    else:
        ffn = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + ffn) + d * c["vocab_size"]


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward of QKᵀ and PV; a token at position i attends i + 1
    keys, (seq + 1) / 2 on average."""
    width = c["num_attention_heads"] * c["head_dim"]
    forward = 2 * 2 * width * (seq + 1) / 2
    return 3 * forward * c["num_hidden_layers"]


def flops_per_token(c: dict, seq: int) -> float:
    return 6 * matmul_weights_per_token(c) + attention_flops_per_token(c, seq)
