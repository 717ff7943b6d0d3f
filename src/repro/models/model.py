"""Top-level model: init / apply for every architecture family.

``score(cfg, params, batch)`` is the scoring function ``h(w; x) ∈ [0, 1]``
that CoDA maximizes AUC for (Assumption 1(iv) of the paper): backbone →
masked mean-pool → linear → sigmoid.  ``lm_logits`` exposes the LM head used
by the serving path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, resnet
from repro.models.embeddings import apply_norm, embed, init_embed, init_norm


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {}
    if cfg.family == "cnn":
        p["backbone"] = resnet.init_resnet(ks[0], cfg, dtype=dtype)
        p["score_head"] = _init_score(ks[1], d, dtype)
        return p
    if cfg.family == "mlp":
        dims = [cfg.n_features] + [d] * cfg.n_layers
        p["mlp"] = [
            {"w": jax.random.normal(k, (di, do), dtype) * di ** -0.5,
             "b": jnp.zeros((do,), dtype)}
            for k, di, do in zip(jax.random.split(ks[0], cfg.n_layers),
                                 dims[:-1], dims[1:])]
        p["score_head"] = _init_score(ks[1], d, dtype)
        return p

    p["embed"] = init_embed(ks[0], cfg.vocab_size, d, dtype)
    if cfg.family == "ssm":
        p["layers"] = blocks.init_xlstm_layers(ks[1], cfg, dtype=dtype)
    else:
        p["layers"] = blocks.init_stack(ks[1], cfg,
                                        cfg.n_layers - cfg.dense_layers,
                                        "xdecoder" if cfg.is_encoder_decoder else "decoder",
                                        dtype=dtype)
    if cfg.dense_layers:
        p["dense_layers"] = blocks.init_stack(
            ks[7], blocks.dense_prefix(cfg), cfg.dense_layers, "decoder",
            dtype=dtype)
    if cfg.is_encoder_decoder:
        p["encoder"] = blocks.init_stack(ks[2], cfg, cfg.encoder_layers, "encoder",
                                         dtype=dtype)
        p["enc_norm"] = init_norm(cfg, d)
        p["enc_in"] = jax.random.normal(ks[5], (d, d), dtype) * d ** -0.5
    if cfg.family == "vlm":
        p["projector"] = jax.random.normal(ks[3], (d, d), dtype) * d ** -0.5
    p["final_norm"] = init_norm(cfg, d)
    p["score_head"] = _init_score(ks[4], d, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(ks[6], (d, cfg.vocab_size), dtype) * d ** -0.5
    return p


def _init_score(key, d, dtype):
    return {"w": jax.random.normal(key, (d, 1), dtype) * d ** -0.5,
            "b": jnp.zeros((1,), jnp.float32)}


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------
def backbone(cfg: ModelConfig, params, batch, *, use_window: bool = False,
             train: bool = False, impl: str = "auto"):
    """Returns (hidden [B, S', d], moe_aux scalar)."""
    if cfg.family == "cnn":
        images = batch["images"]
        B, s, _ = images.shape
        hw = int(round(s ** 0.5))
        x = images.reshape(B, hw, hw, 3)
        return resnet.apply_resnet(cfg, params["backbone"], x)[:, None, :], jnp.zeros((), jnp.float32)

    if cfg.family == "mlp":
        x = batch["features"]
        for lp in params["mlp"]:
            x = jax.nn.relu(x @ lp["w"] + lp["b"])
        return x[:, None, :], jnp.zeros((), jnp.float32)

    if cfg.family == "audio":
        return _encdec(cfg, params, batch, train=train, impl=impl)

    if cfg.family == "vlm":
        patches = batch["patches"] @ params["projector"]
        tok = embed(params["embed"], batch["tokens"])
        x = jnp.concatenate([patches.astype(tok.dtype), tok], axis=1)
    else:
        x = embed(params["embed"], batch["tokens"])

    B, S = x.shape[:2]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    if cfg.family == "ssm":
        h, aux = blocks.apply_xlstm_layers(cfg, params["layers"], x)
    else:
        windows = blocks.layer_windows(cfg, S, use_window)
        if cfg.dense_layers:
            x, _ = blocks.apply_stack(
                blocks.dense_prefix(cfg), params["dense_layers"], x, positions,
                windows[:cfg.dense_layers], train=train, impl=impl)
            windows = windows[cfg.dense_layers:]
        h, aux = blocks.apply_stack(cfg, params["layers"], x, positions, windows,
                                    train=train, impl=impl)
    return apply_norm(cfg, params["final_norm"], h), aux


def _encdec_encoder(cfg: ModelConfig, params, frames, *, train: bool = False,
                    impl: str = "auto"):
    x = frames @ params["enc_in"]
    Se = x.shape[1]
    pos_e = jnp.arange(Se, dtype=jnp.int32)[None, :]
    wins_e = jnp.full((cfg.encoder_layers,), -1, jnp.int32)
    enc, aux_e = blocks.apply_stack(cfg, params["encoder"], x, pos_e, wins_e,
                                    kind="encoder", causal=False, train=train,
                                    impl=impl)
    enc = apply_norm(cfg, params["enc_norm"], enc)
    return enc, aux_e


def _encdec(cfg: ModelConfig, params, batch, *, train: bool, impl: str):
    enc, aux_e = _encdec_encoder(cfg, params, batch["frames"], train=train,
                                 impl=impl)

    tok = embed(params["embed"], batch["tokens"])
    Sd = tok.shape[1]
    pos_d = jnp.arange(Sd, dtype=jnp.int32)[None, :]
    wins_d = jnp.full((cfg.n_layers,), -1, jnp.int32)
    h, aux_d = blocks.apply_stack(cfg, params["layers"], tok, pos_d, wins_d,
                                  kind="xdecoder", causal=True, enc_out=enc,
                                  train=train, impl=impl)
    return apply_norm(cfg, params["final_norm"], h), aux_e + aux_d


def score(cfg: ModelConfig, params, batch, *, use_window: bool = False,
          train: bool = False, impl: str = "auto"):
    """h(w; x) ∈ [0,1] per example.  Returns (scores [B], moe_aux)."""
    h, aux = backbone(cfg, params, batch, use_window=use_window, train=train,
                      impl=impl)
    pooled = jnp.mean(h, axis=1)  # [B, d]
    sh = params["score_head"]
    logit = (pooled @ sh["w"])[:, 0].astype(jnp.float32) + sh["b"][0]
    return jax.nn.sigmoid(logit), aux


def prefill_step(cfg: ModelConfig, params, batch, *, use_window: bool = False,
                 impl: str = "auto"):
    """Inference prefill: forward the full prompt batch, emitting the stacked
    per-layer KV caches [L, B, S, KV, hd] (what a decode session consumes),
    the last-position logits, and the AUC score.

    SSM/xLSTM layers have O(1) recurrent state instead of a length-S cache;
    for those this returns kv=None (state bytes are negligible and the decode
    path rebuilds them)."""
    if cfg.family in ("ssm", "cnn", "mlp"):
        h, _ = backbone(cfg, params, batch, use_window=use_window, impl=impl)
        kv = None
    elif cfg.family == "audio":
        enc, _ = _encdec_encoder(cfg, params, batch["frames"], impl=impl)
        tok = embed(params["embed"], batch["tokens"])
        Sd = tok.shape[1]
        pos_d = jnp.arange(Sd, dtype=jnp.int32)[None, :]
        wins_d = jnp.full((cfg.n_layers,), -1, jnp.int32)
        h, _, kv = blocks.apply_stack(cfg, params["layers"], tok, pos_d, wins_d,
                                      kind="xdecoder", causal=True, enc_out=enc,
                                      impl=impl, return_kv=True)
        h = apply_norm(cfg, params["final_norm"], h)
    else:
        if cfg.family == "vlm":
            patches = batch["patches"] @ params["projector"]
            tok = embed(params["embed"], batch["tokens"])
            x = jnp.concatenate([patches.astype(tok.dtype), tok], axis=1)
        else:
            x = embed(params["embed"], batch["tokens"])
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        windows = blocks.layer_windows(cfg, S, use_window)
        h, _, kv = blocks.apply_stack(cfg, params["layers"], x, positions,
                                      windows, impl=impl, return_kv=True)
        h = apply_norm(cfg, params["final_norm"], h)
    logits = lm_logits(cfg, params, h[:, -1]) if cfg.vocab_size else None
    sh = params["score_head"]
    pooled = jnp.mean(h, axis=1)
    s = jax.nn.sigmoid((pooled @ sh["w"])[:, 0].astype(jnp.float32) + sh["b"][0])
    return s, logits, kv


def lm_logits(cfg: ModelConfig, params, hidden):
    if cfg.tie_embeddings or "lm_head" not in params:
        return hidden @ params["embed"]["table"].T
    return hidden @ params["lm_head"]


# --------------------------------------------------------------------------
# parameter counting (no allocation — eval_shape)
# --------------------------------------------------------------------------
def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    import math
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))
    if active_only and cfg.moe is not None:
        m = cfg.moe
        expert_params = (3 * m.n_held * cfg.d_model * cfg.d_ff
                         * (cfg.n_layers - cfg.dense_layers))
        inactive = expert_params * (1 - m.top_k / m.n_experts)
        total -= int(inactive)
    return int(total)
