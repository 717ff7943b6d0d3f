"""Grouped-query and multi-head latent attention: parameter init,
train/prefill apply (delegating the score/softmax/value contraction to
``repro.kernels.ops.attention``), and, for grouped-query attention,
single-token decode against a KV cache.

Latent attention (``cfg.kv_lora_rank`` > 0, DeepSeek-V2/V3 without query
compression): ``q = x W_q`` per head ``[nope | rope]``; ``[c_kv, k_rope] =
x W_kv_a``, ``c_kv`` RMS-normed; ``[k_nope | v] = c_kv W_kv_b`` per head; the
rope parts rotate by adjacent pairs, ``k_rope`` is one head shared by all;
scores over the whole ``head_dim``-wide query and key; values
``v_head_dim`` wide.  Its train/prefill path is the only one: a latent
cache for decode does not exist yet.

Cache layouts (per layer):
  * full   — k/v ``[B, S, KV, hd]`` plus ``pos [B, S]`` (position held by each
             slot, -1 = empty).
  * ring   — k/v ``[B, W, KV, hd]`` plus ``pos [B, W]``; slot = position % W.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.embeddings import apply_norm, apply_rope, rotate_adjacent

# DeepSeek's RMSNorm on the key/value latent keeps its class default
MLA_LATENT_EPS = 1e-6


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, rr, hv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    nope = hd - rr
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": jax.random.normal(kq, (d, H * hd), dtype) * d ** -0.5,
        "w_kv_a": jax.random.normal(ka, (d, r + rr), dtype) * d ** -0.5,
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "w_kv_b": jax.random.normal(kb, (r, H * (nope + hv)), dtype) * r ** -0.5,
        "wo": jax.random.normal(ko, (H * hv, d), dtype) * (H * hv) ** -0.5,
    }


def _mla_qkv(cfg: ModelConfig, p, x, positions):
    """q, k [B, S, H, head_dim] and v [B, S, H, v_head_dim]."""
    B, S = x.shape[:2]
    H, hd, rr = cfg.n_heads, cfg.head_dim, cfg.qk_rope_head_dim
    r, nope = cfg.kv_lora_rank, hd - rr
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    kv_a = x @ p["w_kv_a"]
    c = apply_norm(cfg, p["kv_norm"], kv_a[..., :r], eps=MLA_LATENT_EPS)
    kv = (c @ p["w_kv_b"]).reshape(B, S, H, -1)
    k_rope = rotate_adjacent(kv_a[..., None, r:], positions, cfg.rope_base)
    q = jnp.concatenate([q[..., :nope], rotate_adjacent(
        q[..., nope:], positions, cfg.rope_base)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, H, rr))], axis=-1)
    return q, k, kv[..., nope:]


def init_attention(key, cfg: ModelConfig, *, cross: bool = False, dtype=jnp.float32):
    if cfg.kv_lora_rank:
        return init_mla(key, cfg, dtype=dtype)
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(kq, (d, H * hd), dtype) * s,
        "wk": jax.random.normal(kk, (d, KV * hd), dtype) * s,
        "wv": jax.random.normal(kv, (d, KV * hd), dtype) * s,
        "wo": jax.random.normal(ko, (H * hd, d), dtype) * (H * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    del cross  # same shapes for cross attention
    return p


def _project_qkv(cfg: ModelConfig, p, xq, xkv):
    B, Sq = xq.shape[:2]
    Skv = xkv.shape[1]
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


@jax.named_scope("attention")
def attend(cfg: ModelConfig, p, x, positions, *, window: int | None, causal=True,
           x_kv=None, kv_positions=None, impl="auto", return_kv: bool = False):
    """Train/prefill attention.  ``x``: [B, S, d].  Returns [B, S, d]
    (and, with ``return_kv``, the rotated K/V for cache emission)."""
    if cfg.kv_lora_rank:
        if return_kv or x_kv is not None:
            raise NotImplementedError(
                "latent attention has no cache or cross-attention path")
        q, k, v = _mla_qkv(cfg, p, x, positions)
    else:
        xkv = x if x_kv is None else x_kv
        q, k, v = _project_qkv(cfg, p, x, xkv)
        if x_kv is None:  # self attention gets RoPE
            q = apply_rope(cfg, q, positions)
            k = apply_rope(cfg, k, positions if kv_positions is None
                           else kv_positions)
    o = kops.attention(q, k, v, causal=causal, window=window, impl=impl)
    B, S = x.shape[:2]
    out = o.reshape(B, S, -1) @ p["wo"]
    if return_kv:
        return out, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
    return out


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, B: int, S: int, *, ring: bool, dtype=jnp.bfloat16):
    """KV cache; ``dtype=jnp.int8`` enables the quantized-cache variant
    (beyond-paper §Perf knob): per-(slot, head) fp32 scales, 1 byte/element
    on the HBM stream that dominates decode."""
    W = min(S, cfg.window) if ring else S
    c = {
        "k": jnp.zeros((B, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((B, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((B, W), -1, jnp.int32),
    }
    if dtype == jnp.int8:
        c["k_scale"] = jnp.zeros((B, W, cfg.n_kv_heads), jnp.float32)
        c["v_scale"] = jnp.zeros((B, W, cfg.n_kv_heads), jnp.float32)
    return c


def _quantize_kv(x):
    """x: [B, KV, hd] -> (int8, scale [B, KV])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_step(cfg: ModelConfig, p, cache, x, positions, *, window: int | None,
                update_cache=True):
    """One-token decode.  ``x``: [B, 1, d]; ``positions``: [B].

    Returns (out [B, 1, d], new_cache).  The cache may be a ring buffer
    (its length < positions is allowed); masking is driven by the per-slot
    ``pos`` array, so stale ring slots and empty slots never contribute.
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x, x)
    q = apply_rope(cfg, q, positions[:, None])
    k_new = apply_rope(cfg, k_new, positions[:, None])

    W = cache["k"].shape[1]
    slot = positions % W
    bidx = jnp.arange(B)
    quant = cache["k"].dtype == jnp.int8
    new_cache = dict(cache)
    if update_cache:
        if quant:
            kq, ks = _quantize_kv(k_new[:, 0])
            vq, vs = _quantize_kv(v_new[:, 0])
            k_all = cache["k"].at[bidx, slot].set(kq)
            v_all = cache["v"].at[bidx, slot].set(vq)
            new_cache["k_scale"] = cache["k_scale"].at[bidx, slot].set(ks)
            new_cache["v_scale"] = cache["v_scale"].at[bidx, slot].set(vs)
        else:
            k_all = cache["k"].at[bidx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
            v_all = cache["v"].at[bidx, slot].set(v_new[:, 0].astype(cache["v"].dtype))
        pos_all = cache["pos"].at[bidx, slot].set(positions)
    else:
        k_all, v_all, pos_all = cache["k"], cache["v"], cache["pos"]

    # [B, KV, G, hd] grouped query
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q[:, 0].reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
    kf = k_all.astype(jnp.float32)
    vf = v_all.astype(jnp.float32)
    if quant:
        kf = kf * new_cache["k_scale"][..., None]
        vf = vf * new_cache["v_scale"][..., None]
    scores = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        kf) / (cfg.head_dim ** 0.5)
    valid = (pos_all >= 0) & (pos_all <= positions[:, None])
    if window is not None:
        valid &= pos_all > (positions[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, vf)
    o = o.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    out = o @ p["wo"]
    new_cache.update(k=k_all, v=v_all, pos=pos_all)
    return out, new_cache


def cross_decode(cfg: ModelConfig, p, enc_k, enc_v, x):
    """Cross-attention during decode: static encoder K/V, query [B, 1, d]."""
    B = x.shape[0]
    q = (x @ p["wq"] + (p.get("bq", 0.0))).reshape(B, 1, cfg.n_heads, cfg.head_dim)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q[:, 0].reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        enc_k.astype(jnp.float32)) / (cfg.head_dim ** 0.5)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, enc_v.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return o @ p["wo"]
