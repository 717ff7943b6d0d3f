"""Token embeddings, normalization layers, and rotary position embeddings.

RoPE variants supported:
  * ``1d``         — full-head rotation (Llama-style).
  * ``partial``    — only the first ``rope_fraction`` of head_dim rotates
                     (StableLM-2 uses 25%).
  * ``2d-partial`` — ChatGLM's two-dimensional RoPE: the head is split in
                     half; only the first half rotates (interleaved pairs),
                     the second half passes through.  Functionally this is a
                     half-rotary with interleaved pairing.
  * ``none``       — no rotation (learned/absolute positions or SSM archs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def init_norm(cfg: ModelConfig, d: int):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dt)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------
def rope_dims(cfg: ModelConfig) -> int:
    """Number of head dimensions that get rotated (even)."""
    if cfg.rope == "none":
        return 0
    n = int(cfg.head_dim * cfg.rope_fraction)
    return n - (n % 2)


def _angles(positions, n_rot: int, base: float):
    # positions: [...]; returns [..., n_rot // 2]
    inv = 1.0 / (base ** (jnp.arange(0, n_rot, 2, dtype=jnp.float32) / n_rot))
    return positions.astype(jnp.float32)[..., None] * inv


def apply_rope(cfg: ModelConfig, x, positions):
    """x: [..., S, n_heads, head_dim]; positions: broadcastable to [..., S]."""
    n_rot = rope_dims(cfg)
    if n_rot == 0:
        return x
    ang = _angles(positions, n_rot, cfg.rope_base)  # [..., S, n_rot/2]
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)  # [..., S, 1, n_rot/2]
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    rot, rest = x[..., :n_rot], x[..., n_rot:]
    if cfg.rope == "2d-partial":
        rot = _rotate_adjacent(rot, cos, sin)
    else:
        # half-split pairing (x_i, x_{i+n/2}) — Llama convention
        half = n_rot // 2
        x1, x2 = rot[..., :half], rot[..., half:]
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot, rest], axis=-1) if rest.shape[-1] else rot


def _rotate_adjacent(x, cos, sin):
    """Interleaved pairing (x0,x1),(x2,x3),... — the ChatGLM and DeepSeek
    convention."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return r.reshape(x.shape)


def rotate_adjacent(x, positions, base: float):
    """Rotate every dimension of x [..., S, heads, n] by adjacent pairs
    (MLA's rotated query and key parts); positions broadcast to [..., S]."""
    ang = _angles(positions, x.shape[-1], base)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    return _rotate_adjacent(x, cos, sin)


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------
def init_embed(key, vocab: int, d: int, dtype=jnp.float32):
    scale = d ** -0.5
    return {"table": jax.random.normal(key, (vocab, d), dtype) * scale}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)
