"""Per-layer blocks and the scan-over-layers stack.

Uniform architectures (dense / moe / vlm / audio / hybrid) store their layer
parameters *stacked* along a leading ``n_layers`` axis and run under
``jax.lax.scan`` (fast compiles at 28–48 layers, natural remat unit).
Heterogeneous stacks (xLSTM's mLSTM/sLSTM mix) use per-layer parameter lists
and an unrolled Python loop.

Sliding-window vs global attention inside a scanned stack is handled with a
*traced* per-layer window size (``-1`` = global); the jnp chunked-attention
implementation masks with it directly, so hybrid stacks stay scannable.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import xlstm as xl
from repro.models.attention import attend, init_attention
from repro.models.embeddings import apply_norm, init_norm
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.moe import apply_moe, init_moe
from repro.models.ssm import apply_ssm, init_ssm


def layer_windows(cfg: ModelConfig, S: int, use_window: bool) -> jnp.ndarray:
    """Per-layer effective window sizes, ``-1`` meaning full/global."""
    if cfg.window_mode == "none" or (cfg.window_mode == "optional" and not use_window):
        return jnp.full((cfg.n_layers,), -1, jnp.int32)
    if cfg.window_mode == "optional":
        return jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
    # all_but_global (hymba): layer 0, every global_attn_every-th, and last are global
    idx = jnp.arange(cfg.n_layers)
    g = (idx % max(cfg.global_attn_every, 1) == 0) | (idx == cfg.n_layers - 1)
    return jnp.where(g, -1, cfg.window).astype(jnp.int32)


def layer_windows_static(cfg: ModelConfig, use_window: bool):
    """Python-level per-layer windows (int | None) for the unrolled decode
    path, mirroring ``layer_windows``."""
    if cfg.window_mode == "none" or (cfg.window_mode == "optional" and not use_window):
        return [None] * cfg.n_layers
    if cfg.window_mode == "optional":
        return [cfg.window] * cfg.n_layers
    out = []
    for i in range(cfg.n_layers):
        g = (i % max(cfg.global_attn_every, 1) == 0) or (i == cfg.n_layers - 1)
        out.append(None if g else cfg.window)
    return out


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_layer(key, cfg: ModelConfig, kind: str, dtype=jnp.float32):
    """kind: decoder | encoder | xdecoder (decoder w/ cross attention)."""
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {"norm1": init_norm(cfg, d), "norm2": init_norm(cfg, d)}
    p["attn"] = init_attention(ks[0], cfg, dtype=dtype)
    if kind == "xdecoder":
        p["norm_x"] = init_norm(cfg, d)
        p["cross"] = init_attention(ks[1], cfg, cross=True, dtype=dtype)
    if cfg.family == "moe" and kind == "decoder":
        p["moe"] = init_moe(ks[2], cfg, dtype=dtype)
    else:
        p["mlp"] = init_mlp(ks[3], cfg, dtype=dtype)
    if cfg.family == "hybrid" and kind == "decoder":
        p["ssm"] = init_ssm(ks[4], cfg, dtype=dtype)
        p["norm_h"] = init_norm(cfg, d)
    return p


def dense_prefix(cfg: ModelConfig) -> ModelConfig:
    """The configuration of the leading dense layers (``cfg.dense_layers``):
    the same attention, an MLP of their own width, no experts."""
    return dataclasses.replace(cfg, family="dense", moe=None,
                               d_ff=cfg.dense_layers_d_ff,
                               n_layers=cfg.dense_layers, dense_layers=0)


def init_stack(key, cfg: ModelConfig, n_layers: int, kind: str, dtype=jnp.float32):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_layer(k, cfg, kind, dtype=dtype))(keys)


def xlstm_layer_kinds(cfg: ModelConfig):
    """Static per-layer kind tuple ("mlstm" | "slstm") — xLSTM[7:1] style."""
    kinds = []
    for i in range(cfg.n_layers):
        slstm = cfg.slstm_every > 0 and (i % cfg.slstm_every == cfg.slstm_every - 1)
        kinds.append("slstm" if slstm else "mlstm")
    return tuple(kinds)


def init_xlstm_layers(key, cfg: ModelConfig, dtype=jnp.float32):
    layers = []
    kinds = xlstm_layer_kinds(cfg)
    for kind, k in zip(kinds, jax.random.split(key, cfg.n_layers)):
        core = (xl.init_slstm if kind == "slstm" else xl.init_mlstm)(k, cfg, dtype=dtype)
        layers.append({"norm1": init_norm(cfg, cfg.d_model), "core": core})
    return layers


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------
def apply_layer(cfg: ModelConfig, p, x, positions, window, *, kind: str,
                causal: bool, enc_out=None, train: bool = False,
                impl: str = "auto", return_kv: bool = False):
    """One block.  ``window``: traced int32 scalar, -1 = full attention.

    Returns (x, aux, kv) where aux is the MoE load-balance loss (0 otherwise)
    and kv the (K, V) pair for cache emission (None unless return_kv).
    """
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, p["norm1"], x)
    a = attend(cfg, p["attn"], h, positions, window=window, causal=causal,
               impl=impl, return_kv=return_kv)
    kv = None
    if return_kv:
        a, kv = a
    if cfg.family == "hybrid" and "ssm" in p:
        s = apply_ssm(cfg, p["ssm"], apply_norm(cfg, p["norm_h"], x))
        a = 0.5 * (a + s)
    x = x + a
    if "cross" in p:
        hx = apply_norm(cfg, p["norm_x"], x)
        cx = attend(cfg, p["cross"], hx, positions, window=None, causal=False,
                    x_kv=enc_out, impl=impl)
        x = x + cx
    h2 = apply_norm(cfg, p["norm2"], x)
    if "moe" in p:
        y, aux = apply_moe(cfg, p["moe"], h2, train=train, impl=impl)
    else:
        y = apply_mlp(cfg, p["mlp"], h2)
    return x + y, aux, kv


def apply_stack(cfg: ModelConfig, stacked, x, positions, windows, *,
                kind: str = "decoder", causal: bool = True, enc_out=None,
                train: bool = False, impl: str = "auto",
                return_kv: bool = False):
    """Scan the stacked layers.  Returns (hidden, total_aux) — plus stacked
    per-layer (K, V) caches [L, B, S, KV, hd] when ``return_kv`` (the
    inference-prefill path)."""

    def body(carry, layer):
        xc, aux = carry
        lp, w = layer
        xn, a, kv = apply_layer(cfg, lp, xc, positions, w, kind=kind,
                                causal=causal, enc_out=enc_out, train=train,
                                impl=impl, return_kv=return_kv)
        return (xn, aux + a), kv

    if train:
        body = jax.checkpoint(body)
    from repro import flags
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 (stacked, windows),
                                 unroll=flags.scan_unroll())
    if return_kv:
        return x, aux, kvs
    return x, aux


def apply_xlstm_layers(cfg: ModelConfig, layers, x):
    for kind, lp in zip(xlstm_layer_kinds(cfg), layers):
        h = apply_norm(cfg, lp["norm1"], x)
        if kind == "slstm":
            x = x + xl.apply_slstm(cfg, lp["core"], h)
        else:
            x = x + xl.apply_mlstm(cfg, lp["core"], h)
    return x, jnp.zeros((), jnp.float32)
