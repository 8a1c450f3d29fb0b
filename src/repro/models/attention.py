"""GQA attention with Megatron-style tensor parallelism, causal attention
for train/prefill, and a sequence-sharded KV cache with logsumexp merging
for decode.

Which train/prefill core runs where (:func:`_attend`):
  * lowered for the TPU, full causal attention (no window) at a sequence
    that a kernel block divides and a head_dim of whole 128-lane tiles
    runs the Pallas flash kernels of ``repro.kernels.flash_attention``
    (``flash_attention.applies``), on the unexpanded K/V where the local
    q head → kv head map is ``arange // group``;
  * everything else (other platforms, sliding windows, unaligned shapes
    such as MusicGen's head_dim 64) runs the chunked XLA path, one
    ``q_chunk``-row block of queries at a time; ``q_chunk`` has no effect
    on the kernel path.

Sharding:
  * Q heads are padded to a multiple of ``model_shards`` and column-split;
    padded heads are masked out of the output (their params receive zero
    gradient and never train).
  * K/V projections are column-split as plain matrices (not head-aligned)
    and all-gathered over the model axis before attention — the standard
    Megatron treatment when ``num_kv_heads < tp`` (uniform path here; the
    kv-head-sharded variant is a hill-climb optimization).
  * The decode KV cache is sharded over ``ctx.seq_axes``; each shard attends
    its local chunk and partial softmaxes merge via pmax/psum (flash-decode).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.dist import MeshCtx
from repro.core.matrixize import MatrixSpec, NONE as SPEC_NONE
from repro.kernels import flash_attention
from repro.models import common
from repro.configs.base import ModelConfig

NEG_INF = -1e30


def padded_heads(cfg: ModelConfig, model_shards: int) -> int:
    h = cfg.num_heads
    return ((h + model_shards - 1) // model_shards) * model_shards


def kv_map(cfg: ModelConfig, model_shards: int):
    """Static q-head → kv-head index map over the padded head range."""
    group = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
    return [min(i, cfg.num_heads - 1) // group for i in range(padded_heads(cfg, model_shards))]


def init(key, cfg: ModelConfig, model_shards: int, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    hp = padded_heads(cfg, model_shards)
    d = cfg.d_model
    kq, kk, kv_, ko = jax.random.split(key, 4)
    params = {
        "wq": common.dense_init(kq, (d, hp * hd), d, dtype),
        "wk": common.dense_init(kk, (d, cfg.num_kv_heads * hd), d, dtype),
        "wv": common.dense_init(kv_, (d, cfg.num_kv_heads * hd), d, dtype),
        "wo": common.dense_init(ko, (hp * hd, d), hp * hd, dtype),
    }
    if cfg.qk_norm:
        params["q_norm"] = common.rmsnorm_init(hd, dtype)
        params["k_norm"] = common.rmsnorm_init(hd, dtype)
    return params


def pspecs(cfg: ModelConfig):
    s = {
        "wq": P(None, "model"),
        "wk": P(None, "model"),
        "wv": P(None, "model"),
        "wo": P("model", None),
    }
    if cfg.qk_norm:
        s["q_norm"] = P(None)
        s["k_norm"] = P(None)
    return s


def mspecs(cfg: ModelConfig):
    s = {k: MatrixSpec("matrix", 0) for k in ("wq", "wk", "wv", "wo")}
    if cfg.qk_norm:
        s["q_norm"] = SPEC_NONE
        s["k_norm"] = SPEC_NONE
    return s


# ---------------------------------------------------------------------------
# train / prefill forward
# ---------------------------------------------------------------------------

def forward(params, x, cfg: ModelConfig, ctx: MeshCtx, *, q_chunk: int = 512,
            window: int = 0):
    """Causal self-attention. x: (B, S, d) replicated over the model axis.

    ``window`` > 0 enables sliding-window attention (sub-quadratic)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    hl = params["wq"].shape[1] // hd          # local (padded) head count
    scale = 1.0 / math.sqrt(hd)

    shards = ctx.model_size()
    group = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
    local_kv = (cfg.tp_local_kv and cfg.num_kv_heads % shards == 0
                and cfg.num_heads % shards == 0)

    # replicated x enters the column-parallel projections here: identity
    # forward, psum(model) on the backward cotangent (see common.grad_synced)
    x = common.grad_synced(x, ctx)

    q = (x @ params["wq"]).reshape(b, s, hl, hd)
    if local_kv:
        # kv heads shard evenly: shard m owns q heads [m·hl, (m+1)·hl) and
        # kv heads [m·kvl, (m+1)·kvl) with hl = group·kvl, so every local q
        # head's kv head is local — no all-gather.
        kvl = cfg.num_kv_heads // shards
        k = (x @ params["wk"]).reshape(b, s, kvl, hd)
        v = (x @ params["wv"]).reshape(b, s, kvl, hd)
    else:
        k = ctx.all_gather_model(x @ params["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
        v = ctx.all_gather_model(x @ params["wv"]).reshape(b, s, cfg.num_kv_heads, hd)

    if cfg.qk_norm:
        q = common.rmsnorm(q, params["q_norm"])
        k = common.rmsnorm(k, params["k_norm"])

    positions = jnp.arange(s)
    q = common.apply_rope(q, positions[None, :], cfg.rope_theta)
    k = common.apply_rope(k, positions[None, :], cfg.rope_theta)

    # map local q heads to kv heads (global head id depends on the shard)
    head0 = ctx.model_index() * hl
    gheads = head0 + jnp.arange(hl)
    if local_kv:
        kv_idx = jnp.arange(hl) // group       # local kv index
    else:
        kv_idx = jnp.minimum(gheads, cfg.num_heads - 1) // group
    # the map is arange(hl) // group when the kv heads are local or unsharded
    grouped = local_kv or shards == 1

    out = _attend(q, k, v, kv_idx, grouped, q_chunk=q_chunk, window=window,
                  scale=scale)
    # mask padded heads so they contribute nothing (and get no gradient)
    out = jnp.where((gheads < cfg.num_heads)[None, None, :, None], out, 0.0)
    out = out.reshape(b, s, hl * hd)
    return ctx.psum_model(out @ params["wo"])


def _attend(q, k, v, kv_idx, grouped, *, q_chunk, window, scale):
    """The attention core: (B, S, hl, hd) outputs of q (B, S, hl, hd) over
    k, v (B, S, kv, hd), q head h reading kv head ``kv_idx[h]``.

    Lowered for the TPU, full causal attention at aligned shapes
    (``flash_attention.applies``) runs the flash kernels, on the
    unexpanded K/V where ``grouped`` says ``kv_idx`` is ``arange // group``.
    Everything else (other platforms, sliding windows, unaligned shapes)
    runs :func:`_chunked`."""
    expand = lambda x: jnp.take(x, kv_idx, axis=2)       # (B, S, hl, hd)

    def chunked(q, k, v):
        return _chunked(q, expand(k), expand(v), q_chunk, window, scale)

    if not flash_attention.applies(q.shape[1], q.shape[3], window):
        return chunked(q, k, v)

    def flash(q, k, v):
        if not grouped:
            k, v = expand(k), expand(v)
        return flash_attention.causal_attention(q, k, v)

    return lax.platform_dependent(q, k, v, tpu=flash, default=chunked)


def _chunked(q, k_h, v_h, q_chunk, window, scale):
    """Attention one q chunk at a time in XLA, K/V expanded to q's heads."""
    b, s, hl, hd = q.shape
    qc = min(q_chunk, s)
    n_chunks = (s + qc - 1) // qc
    s_pad = n_chunks * qc
    q_padded = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    q_chunks = q_padded.reshape(b, n_chunks, qc, hl, hd).transpose(1, 0, 2, 3, 4)

    if window and window < s:
        out_chunks = _windowed_chunks(q_chunks, k_h, v_h, qc, window, scale)
    else:
        out_chunks = _full_chunks(q_chunks, k_h, v_h, qc, scale)

    return out_chunks.transpose(1, 0, 2, 3, 4).reshape(b, s_pad, hl, hd)[:, :s]


def _full_chunks(q_chunks, k, v, qc, scale):
    """Causal attention one q chunk at a time.

    The chunk body is checkpointed: without it the scan's backward keeps
    every chunk's (B, heads, qc, S) probabilities at once, the full S×S
    score tensor the chunking exists to avoid (4 GiB a sequence in f32 at
    32 heads and S=4096)."""
    s = k.shape[1]

    def one(carry, args):
        i, qck = args
        # scores: (B, hl, qc, S)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qck, k) * scale
        qpos = i * qc + jnp.arange(qc)
        kpos = jnp.arange(s)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return carry, out

    _, outs = lax.scan(jax.checkpoint(one), None,
                       (jnp.arange(q_chunks.shape[0]), q_chunks))
    return outs


def _windowed_chunks(q_chunks, k, v, qc, window, scale):
    """Sliding-window: each q chunk attends a static (window+qc)-wide kv slice."""
    s = k.shape[1]
    wpad = ((window + qc - 1) // qc) * qc      # align slice starts
    kv_span = wpad + qc
    # left-pad K/V so every chunk can take a static-size slice
    kp = jnp.pad(k, ((0, 0), (wpad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (wpad, 0), (0, 0), (0, 0)))

    def one(carry, args):
        i, qck = args
        start = i * qc  # in padded coords this is (i*qc + wpad) - wpad
        ks = lax.dynamic_slice_in_dim(kp, start, kv_span, axis=1)
        vs = lax.dynamic_slice_in_dim(vp, start, kv_span, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qck, ks) * scale
        qpos = i * qc + jnp.arange(qc)                       # global q positions
        kpos = start + jnp.arange(kv_span) - wpad            # global kv positions
        mask = (qpos[:, None] >= kpos[None, :]) & \
               (qpos[:, None] - kpos[None, :] < window) & (kpos[None, :] >= 0)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vs)
        return carry, out

    _, outs = lax.scan(jax.checkpoint(one), None,
                       (jnp.arange(q_chunks.shape[0]), q_chunks))
    return outs


# ---------------------------------------------------------------------------
# decode with a sequence-sharded KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_local: int, seq_local: int,
               dtype=jnp.float32):
    """Local KV cache slice for one attention layer (unstacked)."""
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch_local, seq_local, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch_local, seq_local, cfg.num_kv_heads, hd), dtype),
    }


def cache_pspecs(batch_axes, seq_axes) -> dict:
    ba = batch_axes if batch_axes else None
    sa = seq_axes if seq_axes else None
    return {"k": P(ba, sa, None, None), "v": P(ba, sa, None, None)}


def decode(params, x, cache, pos, cfg: ModelConfig, ctx: MeshCtx, *,
           window: int = 0):
    """One-token decode. x: (B_local, 1, d) replicated over model & seq axes.

    cache k/v: (B_local, S_local, kv, hd), seq-sharded over ``ctx.seq_axes``.
    ``pos``: scalar int32 — the position of the new token.
    Returns (attn_out (B,1,d), new_cache)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    hl = params["wq"].shape[1] // hd
    hp = hl * ctx.model_size() if ctx.model_axis else hl
    scale = 1.0 / math.sqrt(hd)
    s_local = cache["k"].shape[1]

    # --- project the new token; gather full heads on every shard -----------
    q = ctx.all_gather_model(x @ params["wq"]).reshape(b, 1, hp, hd)
    k_new = ctx.all_gather_model(x @ params["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
    v_new = ctx.all_gather_model(x @ params["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)

    if cfg.qk_norm:
        q = common.rmsnorm(q, params["q_norm"])
        k_new = common.rmsnorm(k_new, params["k_norm"])

    posv = jnp.full((1, 1), pos)
    q = common.apply_rope(q, posv, cfg.rope_theta)[:, 0]          # (B, hp, hd)
    k_new = common.apply_rope(k_new, posv, cfg.rope_theta)        # roped at abs pos

    # --- write the new kv into the owning shard's slot ---------------------
    cache_len = s_local * max(ctx.seq_size(), 1)
    slot = pos % cache_len if window else pos                     # ring vs linear
    owner = slot // s_local
    offset = slot % s_local
    mine = owner == ctx.seq_index()
    k_upd = lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), offset, axis=1)
    v_upd = lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), offset, axis=1)
    new_cache = {
        "k": jnp.where(mine, k_upd, cache["k"]),
        "v": jnp.where(mine, v_upd, cache["v"]),
    }

    # --- attend over the local chunk, merge partial softmaxes --------------
    kv = cfg.num_kv_heads
    grouped = (cfg.gqa_grouped_decode and hp == cfg.num_heads
               and cfg.num_heads % max(kv, 1) == 0)
    if grouped:
        # GQA-aware: group q heads by kv head in the contraction instead of
        # materializing the cache expanded to every q head (saves
        # group_size× the kv-cache read traffic per token)
        g = cfg.num_heads // kv
        qg = q.reshape(b, kv, g, hd)
        scores = jnp.einsum(
            "bkgd,bskd->bkgs", qg,
            new_cache["k"].astype(q.dtype)) * scale
        scores = scores.reshape(b, hp, s_local)
    else:
        kvm = jnp.asarray(kv_map(cfg, 1 if not ctx.model_axis else ctx.model_size()))
        kvm = kvm[:hp]
        k_loc = jnp.take(new_cache["k"], kvm, axis=2)   # (B, S_local, hp, hd)
        v_loc = jnp.take(new_cache["v"], kvm, axis=2)

        scores = jnp.einsum("bhd,bkhd->bhk", q, k_loc.astype(q.dtype)) * scale

    slots_g = ctx.seq_index() * s_local + jnp.arange(s_local)
    if window:
        stored = pos - ((pos - slots_g) % cache_len)
        valid = stored >= 0
    else:
        valid = slots_g <= pos
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)

    m_loc = jnp.max(scores, axis=-1)                             # (B, hp)
    m_glob = ctx.pmax_seq(m_loc)
    p = jnp.exp(scores - m_glob[..., None])
    l_loc = jnp.sum(p, axis=-1)
    if grouped:
        g = cfg.num_heads // kv
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p.reshape(b, kv, g, s_local),
                           new_cache["v"].astype(p.dtype)).reshape(b, hp, hd)
    else:
        o_loc = jnp.einsum("bhk,bkhd->bhd", p, v_loc.astype(p.dtype))
    l_glob = ctx.psum_seq(l_loc)
    o_glob = ctx.psum_seq(o_loc)
    out = o_glob / jnp.maximum(l_glob[..., None], 1e-30)          # (B, hp, hd)

    out = jnp.where((jnp.arange(hp) < cfg.num_heads)[None, :, None], out, 0.0)
    out = out.reshape(b, 1, hp * hd)

    # row-parallel wo: local rows = this shard's slice of the head dim
    rows = params["wo"].shape[0]
    start = ctx.model_index() * rows
    out_slice = lax.dynamic_slice_in_dim(out, start, rows, axis=-1)
    return ctx.psum_model(out_slice @ params["wo"]), new_cache


# ---------------------------------------------------------------------------
# prefill: run the chunked forward AND emit the cache slice for this shard
# ---------------------------------------------------------------------------

def prefill(params, x, cfg: ModelConfig, ctx: MeshCtx, *, q_chunk: int = 512,
            window: int = 0):
    """Forward over the prompt, returning (out, cache_slice).

    The cache slice holds this shard's s_local = S/seq_shards chunk of the
    roped K/V (full kv heads), matching the decode layout."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim

    seq_shards = max(ctx.seq_size(), 1)
    s_local = s // seq_shards
    start = ctx.seq_index() * s_local

    if cfg.tp_local_kv and ctx.model_axis and seq_shards == ctx.model_size():
        # perf: the cache wants row (sequence) distribution of X·W_kv while
        # TP computes its column (head) distribution — that relayout is one
        # all-to-all whose result is S/seq_shards the size of the naive
        # full-sequence all-gather.  (The naive path's gather is shared with
        # forward() by CSE; under tp_local_kv forward keeps kv heads local
        # and needs no gather at all.)
        k = ctx.all_to_all_model(x @ params["wk"], split_axis=1,
                                 concat_axis=2).reshape(
            b, s_local, cfg.num_kv_heads, hd)
        v = ctx.all_to_all_model(x @ params["wv"], split_axis=1,
                                 concat_axis=2).reshape(
            b, s_local, cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            k = common.rmsnorm(k, params["k_norm"])
        positions = start + jnp.arange(s_local)
        k = common.apply_rope(k, positions[None, :], cfg.rope_theta)
        cache = {"k": k, "v": v}
    else:
        k = ctx.all_gather_model(x @ params["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
        v = ctx.all_gather_model(x @ params["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            k = common.rmsnorm(k, params["k_norm"])
        positions = jnp.arange(s)
        k = common.apply_rope(k, positions[None, :], cfg.rope_theta)
        cache = {
            "k": lax.dynamic_slice_in_dim(k, start, s_local, axis=1),
            "v": lax.dynamic_slice_in_dim(v, start, s_local, axis=1),
        }
    out = forward(params, x, cfg, ctx, q_chunk=q_chunk, window=window)
    return out, cache
