"""Moonlight-16B-A3B (DeepSeek-V3 layers) at its published widths, one
chip's expert-parallel share: the program's configuration, weights in the
program's parameter layout, a plain float32 reference forward, and the
model FLOPs of one sequence.

The reference reads nothing of the program.  It follows the published
architecture: token embedding; pre-RMSNorm blocks of multi-head latent
attention (no query compression: per head q = [q_nope 128 | q_rope 64];
[c_kv 512 | k_rope 64] = x W_kv_a, c_kv RMS-normed; per head [k_nope 128 |
v 128] = c_kv W_kv_b; rotary on q_rope and on the one k_rope all heads
share, by adjacent pairs (2i, 2i+1) at rope_theta^(-2i/64); scores
q_nope.k_nope + q_rope.k_rope scaled by 192^-0.5; causal softmax) and of
an MLP: a SwiGLU of width 11,264 in the leading dense layer, then in every
MoE layer the sigmoid router (scores s = sigmoid(x W_r), the top 6 of
s + select_bias chosen, weights the chosen s over their sum times 2.446)
with the held experts' part plus two shared experts as one SwiGLU of width
2 x 1,408; a final RMSNorm, then the CoDA score head: the sigmoid of a
linear score of the mean over positions.

The held share: the router scores all 64 experts; the held experts
[held_offset, held_offset + 8) are computed densely over every token and
masked by their gate weight (no sort, no kernels); the other experts' part
is left out, as in the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def model_config(cfg: dict):
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    return dataclasses.replace(
        base,
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_layers_d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_base=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        moe=dataclasses.replace(
            base.moe, n_experts=cfg["published"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            n_held=cfg["n_routed_experts"], held_offset=cfg["held_offset"],
            dense_residual=True,
            dense_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            routed_scale=cfg["routed_scaling_factor"]))


def inputs(cfg: dict) -> dict:
    return {"kind": "tokens", "vocab_size": cfg["vocab_size"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _mla_weights(key, L, cfg):
    d, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rr, hv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    k = jax.random.split(key, 4)
    return {"wq": _normal(k[0], (L, d, H * (nope + rr)), d),
            "w_kv_a": _normal(k[1], (L, d, r + rr), d),
            "kv_norm": {"scale": jnp.ones((L, r), jnp.float32)},
            "w_kv_b": _normal(k[2], (L, r, H * (nope + hv)), r),
            "wo": _normal(k[3], (L, H * hv, d), H * hv)}


def _swiglu_weights(key, lead, d, ff):
    k = jax.random.split(key, 3)
    return {"w_gate": _normal(k[0], lead + (d, ff), d),
            "w_up": _normal(k[1], lead + (d, ff), d),
            "w_down": _normal(k[2], lead + (ff, d), ff)}


def make_weights(key, cfg: dict):
    """N(0, 1/fan_in) matrices, unit RMSNorm scales, a zero score bias and
    the selection bias drawn N(0, select_bias_std^2): one worker's
    parameters in the program's tree layout (layers stacked on a leading
    axis, the dense layer in a stack of its own)."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    L0 = cfg["first_k_dense_replace"]
    L = cfg["num_hidden_layers"] - L0
    E, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    E_all = cfg["published"]["n_routed_experts"]
    ks = jax.random.split(key, 10)
    norm = lambda *lead: {"scale": jnp.ones(lead + (d,), jnp.float32)}
    moe = dict(_swiglu_weights(ks[0], (L, E), d, ff),
               router=_normal(ks[1], (L, d, E_all), d),
               select_bias=cfg["select_bias_std"] * jax.random.normal(
                   ks[2], (L, E_all), jnp.float32),
               dense=_swiglu_weights(ks[3], (L,), d,
                                     cfg["n_shared_experts"] * ff))
    return {"embed": {"table": _normal(ks[4], (V, d), d)},
            "dense_layers": {"norm1": norm(L0), "norm2": norm(L0),
                             "attn": _mla_weights(ks[5], L0, cfg),
                             "mlp": _swiglu_weights(
                                 ks[6], (L0,), d, cfg["intermediate_size"])},
            "layers": {"norm1": norm(L), "norm2": norm(L),
                       "attn": _mla_weights(ks[7], L, cfg), "moe": moe},
            "final_norm": norm(),
            "score_head": {"w": _normal(ks[8], (d, 1), d),
                           "b": jnp.zeros((1,), jnp.float32)},
            "lm_head": _normal(ks[9], (d, V), d)}


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale.astype(x.dtype)


def _rotary_adjacent(x, theta):
    """Rotate x [B, S, heads, n] by the pairs (2i, 2i + 1), angle
    position * theta^(-2i/n)."""
    S, n = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def _mla(x, a, cfg):
    B, S, d = x.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rr, hv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    w = lambda name: a[name].astype(x.dtype)
    q = (x @ w("wq")).reshape(B, S, H, nope + rr)
    kv_a = x @ w("w_kv_a")
    c = _rms_norm(kv_a[..., :r], a["kv_norm"]["scale"],
                  cfg["kv_latent_norm_eps"])
    kv = (c @ w("w_kv_b")).reshape(B, S, H, nope + hv)
    q_rope = _rotary_adjacent(q[..., nope:], cfg["rope_theta"])
    k_rope = _rotary_adjacent(kv_a[..., None, r:], cfg["rope_theta"])[:, :, 0]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope))
    s = s / jnp.sqrt(jnp.asarray(nope + rr, x.dtype))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.finfo(x.dtype).min)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   kv[..., nope:])
    return o.reshape(B, S, H * hv) @ w("wo")


def _swiglu(x, m):
    w = lambda name: m[name].astype(x.dtype)
    return (jax.nn.silu(x @ w("w_gate")) * (x @ w("w_up"))) @ w("w_down")


def route(xf, m, cfg):
    """Gate weights [T, E_all] of the sigmoid router, 0 for the experts a
    token does not choose."""
    s = jax.nn.sigmoid((xf @ m["router"].astype(xf.dtype)).astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + m["select_bias"], cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) \
        * cfg["routed_scaling_factor"]
    rows = jnp.arange(xf.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(weight)


def _moe(x, m, cfg):
    """The held experts' part, densely over every token and masked by the
    gate weights, plus the shared experts."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    off, E = cfg["held_offset"], cfg["n_routed_experts"]
    gate = route(xf, m, cfg)[:, off:off + E].astype(x.dtype)   # [T, E]
    w = lambda name: m[name].astype(x.dtype)
    h = jnp.einsum("td,edf->tef", xf, w("w_gate"))
    u = jnp.einsum("td,edf->tef", xf, w("w_up"))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, w("w_down"))
    routed = jnp.einsum("te,ted->td", gate, y)
    return (routed + _swiglu(xf, m["dense"])).reshape(B, S, d)


def _block(x, p, cfg, mlp):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, p["norm1"]["scale"], eps), p["attn"], cfg)
    return x + mlp(_rms_norm(x, p["norm2"]["scale"], eps))


def score(params, batch: dict, cfg: dict, dtype=jnp.float32):
    """h(w; x) in (0, 1) for every sequence of ``batch["tokens"]`` [B, S],
    computed in ``dtype``.  Each block is rematerialised in the backward
    pass, which changes what is kept in memory and nothing of the math."""
    x = params["embed"]["table"].astype(dtype)[batch["tokens"]]
    dense = jax.checkpoint(lambda x, p: _block(
        x, p, cfg, lambda h: _swiglu(h, p["mlp"])))
    moe = jax.checkpoint(lambda x, p: _block(
        x, p, cfg, lambda h: _moe(h, p["moe"], cfg)))
    at = lambda tree, i: jax.tree_util.tree_map(lambda l: l[i], tree)
    for i in range(cfg["first_k_dense_replace"]):
        x = dense(x, at(params["dense_layers"], i))
    for i in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        x = moe(x, at(params["layers"], i))
    x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    pooled = jnp.mean(x, axis=1)
    head = params["score_head"]
    logit = pooled @ head["w"][:, 0].astype(dtype) + head["b"][0].astype(dtype)
    return jax.nn.sigmoid(logit.astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------
def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward plus backward FLOPs of one sequence: 2 per multiply-add, times
    3, of the latent attention's projections at every position and of its
    causal attention (query t reads t + 1 keys, 192-wide for scores and
    128-wide for values), of the dense layer's MLP, and in every MoE layer
    of the router, the shared experts and the held experts at the load an
    even router gives them (k x E_held / E expert evaluations per token),
    and of the score head.  The recomputed forward of the rematerialised
    layers is not counted, nor are norms, rotary, softmax, the sort or the
    combine."""
    d, S, H = cfg["hidden_size"], traffic["seq_len"], cfg["num_attention_heads"]
    r, nope, rr, hv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    L = cfg["num_hidden_layers"]
    L_moe = L - cfg["first_k_dense_replace"]
    ff, E, E_all = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                    cfg["published"]["n_routed_experts"])
    mla = d * H * (nope + rr) + d * (r + rr) + r * H * (nope + hv) + H * hv * d
    attn = H * (nope + rr + hv) * S * (S + 1) // 2
    dense = (L - L_moe) * 3 * d * cfg["intermediate_size"]
    routed = cfg["num_experts_per_tok"] * E / E_all * 3 * d * ff
    moe = L_moe * (d * E_all + 3 * d * cfg["n_shared_experts"] * ff + routed)
    return 3.0 * 2.0 * (S * (L * mla + dense + moe) + L * attn + d)
