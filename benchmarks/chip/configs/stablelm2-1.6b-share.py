"""StableLM-2-1.6B at its published widths, one chip's share of its depth:
the program's configuration, weights in the program's parameter layout, a
plain float32 reference forward, and the model FLOPs of one sequence.

The reference reads nothing of the program.  It follows the published
architecture: token embedding, pre-LayerNorm blocks (LayerNorm with bias,
multi-head attention with biases on q, k and v, rotary embedding on the
first quarter of each head by half-split pairs, causal softmax, a SwiGLU
MLP), a final LayerNorm, then the CoDA score head: the sigmoid of a linear
score of the mean over positions.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def model_config(cfg: dict):
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["arch"]),
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope="partial", rope_fraction=cfg["partial_rotary_factor"],
        rope_base=float(cfg["rope_theta"]), qkv_bias=cfg["use_qkv_bias"],
        norm="layernorm", act="swiglu",
        tie_embeddings=cfg["tie_word_embeddings"])


def inputs(cfg: dict) -> dict:
    return {"kind": "tokens", "vocab_size": cfg["vocab_size"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def make_weights(key, cfg: dict):
    """N(0, 1/fan_in) matrices, zero biases, unit LayerNorm scales: one
    worker's parameters in the program's tree layout (layers stacked on a
    leading axis)."""
    d, ff, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    ks = jax.random.split(key, 10)
    ln = lambda *lead: {"scale": jnp.ones(lead + (d,), jnp.float32),
                        "bias": jnp.zeros(lead + (d,), jnp.float32)}
    attn = {"wq": _normal(ks[0], (L, d, d), d),
            "wk": _normal(ks[1], (L, d, d), d),
            "wv": _normal(ks[2], (L, d, d), d),
            "wo": _normal(ks[3], (L, d, d), d)}
    if cfg["use_qkv_bias"]:
        attn.update(bq=jnp.zeros((L, d), jnp.float32),
                    bk=jnp.zeros((L, d), jnp.float32),
                    bv=jnp.zeros((L, d), jnp.float32))
    p = {"embed": {"table": _normal(ks[4], (V, d), d)},
         "layers": {"norm1": ln(L), "norm2": ln(L), "attn": attn,
                    "mlp": {"w_gate": _normal(ks[5], (L, d, ff), d),
                            "w_up": _normal(ks[6], (L, d, ff), d),
                            "w_down": _normal(ks[7], (L, ff, d), ff)}},
         "final_norm": ln(),
         "score_head": {"w": _normal(ks[8], (d, 1), d),
                        "b": jnp.zeros((1,), jnp.float32)}}
    if not cfg["tie_word_embeddings"]:
        p["lm_head"] = _normal(ks[9], (d, V), d)
    return p


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def _rotary(x, n_rot, theta):
    """Rotate the first ``n_rot`` dims of every head: pairs (i, i + n_rot/2)."""
    S = x.shape[1]
    half = n_rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, n_rot, 2, dtype=jnp.float32) / n_rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:n_rot], x[..., n_rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _block(x, p, cfg):
    B, S, d = x.shape
    H = cfg["num_attention_heads"]
    hd = d // H
    n_rot = int(hd * cfg["partial_rotary_factor"])
    n_rot -= n_rot % 2
    eps = cfg["layer_norm_eps"]
    a = p["attn"]
    w = lambda name: a[name].astype(x.dtype)
    h = _layer_norm(x, p["norm1"], eps)
    q, k, v = h @ w("wq"), h @ w("wk"), h @ w("wv")
    if cfg["use_qkv_bias"]:
        q, k, v = q + w("bq"), k + w("bk"), v + w("bv")
    q, k, v = (t.reshape(B, S, H, hd) for t in (q, k, v))
    q = _rotary(q, n_rot, cfg["rope_theta"])
    k = _rotary(k, n_rot, cfg["rope_theta"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(hd, x.dtype))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.finfo(x.dtype).min)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(B, S, d) @ w("wo")
    m = p["mlp"]
    h = _layer_norm(x, p["norm2"], eps)
    y = jax.nn.silu(h @ m["w_gate"].astype(x.dtype)) * (h @ m["w_up"].astype(x.dtype))
    return x + y @ m["w_down"].astype(x.dtype)


def score(params, batch: dict, cfg: dict, dtype=jnp.float32):
    """h(w; x) in (0, 1) for every sequence of ``batch["tokens"]`` [B, S],
    computed in ``dtype``.  Each block is rematerialised in the backward
    pass, which changes what is kept in memory and nothing of the math."""
    x = params["embed"]["table"].astype(dtype)[batch["tokens"]]
    block = jax.checkpoint(lambda x, p: _block(x, p, cfg))
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, jax.tree_util.tree_map(lambda l: l[i], params["layers"]))
    x = _layer_norm(x, params["final_norm"], cfg["layer_norm_eps"])
    pooled = jnp.mean(x, axis=1)
    head = params["score_head"]
    logit = pooled @ head["w"][:, 0].astype(dtype) + head["b"][0].astype(dtype)
    return jax.nn.sigmoid(logit.astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------
def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward plus backward FLOPs of one sequence: 2 per multiply-add of
    the q, k, v, o and MLP projections at every position, of the causal
    attention (query t reads t + 1 keys, for scores and for values) and of
    the score head, times 3.  The recomputed forward of the rematerialised
    blocks is not counted, nor are norms, rotary, softmax or biases."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    S, L = traffic["seq_len"], cfg["num_hidden_layers"]
    proj = S * (4 * d * d + 3 * d * ff)
    attn = 2 * d * S * (S + 1) // 2
    return 3.0 * 2.0 * (L * (proj + attn) + d)
