"""Autoregressive serving: KV-cache construction and the one-token
``serve_step`` that the decode input shapes (decode_32k, long_500k) lower.

The layer loop is *unrolled* in Python (vs the scanned training stack) so
that heterogeneous per-layer cache shapes are possible:
  * full-attention layers   — [B, S, KV, hd] caches,
  * sliding-window layers   — [B, W, KV, hd] ring buffers (this is what makes
    ``long_500k`` sub-quadratic-memory for Hymba and windowed dense archs),
  * mamba branches          — O(1) conv + SSM state,
  * xLSTM blocks            — O(1) matrix/scalar memory, no length-S cache,
  * enc-dec                 — precomputed cross-attention K/V + short self cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import blocks, xlstm as xl
from repro.models.embeddings import apply_norm, embed
from repro.models.mlp import apply_mlp
from repro.models.moe import apply_moe
from repro.models.model import lm_logits
from repro.models.ssm import decode_ssm, init_ssm_state


def _layer_params(params_layers, i: int):
    """Slice layer i out of a stacked layer pytree."""
    return jax.tree_util.tree_map(lambda a: a[i], params_layers)


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------
def _refuse_latent(cfg: ModelConfig):
    if cfg.kv_lora_rank or cfg.dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: latent attention (MLA) and a dense layer prefix "
            "have no decode path yet; the latent cache is a serving change "
            "of its own")


def init_cache(cfg: ModelConfig, B: int, S: int, *, use_window: bool = True,
               dtype=jnp.bfloat16):
    """Cache pytree for a decode session of maximum length S."""
    _refuse_latent(cfg)
    if cfg.family == "ssm":
        layers = []
        for kind in blocks.xlstm_layer_kinds(cfg):
            if kind == "slstm":
                layers.append({"slstm": xl.init_slstm_state(cfg, B)})
            else:
                layers.append({"mlstm": xl.init_mlstm_state(cfg, B)})
        return {"layers": layers}
    wins = blocks.layer_windows_static(cfg, use_window)
    layers = []
    for i in range(cfg.n_layers):
        lc = {"attn": A.init_cache(cfg, B, S, ring=wins[i] is not None, dtype=dtype)}
        if cfg.family == "hybrid":
            lc["ssm"] = init_ssm_state(cfg, B)
        if cfg.is_encoder_decoder:
            # cross-attention K/V over the encoded sequence (filled at prefill)
            lc["enc_k"] = jnp.zeros((B, S, cfg.n_kv_heads, cfg.head_dim), dtype)
            lc["enc_v"] = jnp.zeros((B, S, cfg.n_kv_heads, cfg.head_dim), dtype)
            # decoder self-attention cache is short (S // decoder_fraction)
            lc["attn"] = A.init_cache(cfg, B, max(1, S // cfg.decoder_fraction),
                                      ring=False, dtype=dtype)
        layers.append(lc)
    return {"layers": layers}


def cache_specs(cfg: ModelConfig, B: int, S: int, *, use_window: bool = True,
                dtype=jnp.bfloat16):
    """ShapeDtypeStruct mirror of ``init_cache`` (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda: init_cache(cfg, B, S, use_window=use_window, dtype=dtype))


def encode_for_decode(cfg: ModelConfig, params, cache, frames, *, impl="auto"):
    """Enc-dec archs: run the encoder over frame embeddings and fill every
    decoder layer's cross-attention K/V."""
    from repro.models.model import _encdec_encoder  # local import, small helper

    enc, _ = _encdec_encoder(cfg, params, frames, impl=impl)
    B, Se = enc.shape[:2]
    new_layers = []
    for i in range(cfg.n_layers):
        lp = _layer_params(params["layers"], i)
        lc = dict(cache["layers"][i])
        cp = lp["cross"]
        k = (enc @ cp["wk"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        v = (enc @ cp["wv"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qkv_bias:
            k, v = k + cp["bk"].reshape(1, 1, cfg.n_kv_heads, -1), v + cp["bv"].reshape(1, 1, cfg.n_kv_heads, -1)
        lc["enc_k"] = k.astype(lc["enc_k"].dtype)
        lc["enc_v"] = v.astype(lc["enc_v"].dtype)
        new_layers.append(lc)
    return {"layers": new_layers}


# --------------------------------------------------------------------------
# serve_step
# --------------------------------------------------------------------------
def serve_step(cfg: ModelConfig, params, cache, tokens, positions, *,
               use_window: bool = True, impl: str = "auto"):
    """Decode ONE token.  tokens: [B, 1]; positions: [B].

    Returns (logits [B, vocab], score_logit [B], new_cache).
    """
    _refuse_latent(cfg)
    x = embed(params["embed"], tokens)  # [B, 1, d]
    new_layers = []
    if cfg.family == "ssm":
        for i, kind in enumerate(blocks.xlstm_layer_kinds(cfg)):
            lp = params["layers"][i]
            lc = cache["layers"][i]
            h = apply_norm(cfg, lp["norm1"], x)
            if kind == "slstm":
                o, st = xl.decode_slstm(cfg, lp["core"], lc["slstm"], h)
                new_layers.append({"slstm": st})
            else:
                o, st = xl.decode_mlstm(cfg, lp["core"], lc["mlstm"], h)
                new_layers.append({"mlstm": st})
            x = x + o
    else:
        wins = blocks.layer_windows_static(cfg, use_window)
        for i in range(cfg.n_layers):
            lp = _layer_params(params["layers"], i)
            lc = cache["layers"][i]
            nc = {}
            h = apply_norm(cfg, lp["norm1"], x)
            a, nc["attn"] = A.decode_step(cfg, lp["attn"], lc["attn"], h,
                                          positions, window=wins[i])
            if cfg.family == "hybrid":
                s, nc["ssm"] = decode_ssm(cfg, lp["ssm"],
                                          lc["ssm"], apply_norm(cfg, lp["norm_h"], x))
                a = 0.5 * (a + s)
            x = x + a
            if cfg.is_encoder_decoder:
                hx = apply_norm(cfg, lp["norm_x"], x)
                x = x + A.cross_decode(cfg, lp["cross"], lc["enc_k"], lc["enc_v"], hx)
                nc["enc_k"], nc["enc_v"] = lc["enc_k"], lc["enc_v"]
            h2 = apply_norm(cfg, lp["norm2"], x)
            if "moe" in lp:
                y, _ = apply_moe(cfg, lp["moe"], h2, impl=impl)
            else:
                y = apply_mlp(cfg, lp["mlp"], h2)
            x = x + y
            new_layers.append(nc)

    h = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, h[:, 0])
    sh = params["score_head"]
    score_logit = (h[:, 0] @ sh["w"])[:, 0].astype(jnp.float32) + sh["b"][0]
    return logits, score_logit, {"layers": new_layers}


# --------------------------------------------------------------------------
# prefill (fills the cache from a prompt; used by the serving engine)
# --------------------------------------------------------------------------
def masked_chunk_step(cfg: ModelConfig, params, cache, tokens, positions,
                      n_tokens, *, use_window: bool = True,
                      impl: str = "auto"):
    """Batched chunked prefill/decode: feed each batch row up to C tokens.

    The continuous-batching engine's device step — the same scan over
    ``serve_step`` that ``prefill`` runs, generalized to heterogeneous rows:
    slots mid-prefill consume up to C prompt tokens per call while slots in
    decode (or free slots) consume one (or zero).

      tokens:    [B, C] int32 — row s feeds tokens[s, :n_tokens[s]]
      positions: [B]    int32 — row s's first token lands at positions[s]
      n_tokens:  [B]    int32 — live steps per row (0 => row is idle)

    Rows are independent through the whole model (attention reads only the
    row's own cache line; routing/norms are per-token), so masking is a
    per-row select: step t computes ``serve_step`` for every row but rows
    with ``t >= n_tokens`` keep their previous cache bitwise.  Every cache
    leaf carries the row (slot) axis at dim 0 — the engine-wide contract
    ``ServingEngine._reset_slot`` enforces.

    Returns ``(cache, argmax_tokens [B, C] int32, score_logits [B, C] f32)``;
    outputs at dead steps (t >= n_tokens[s]) are garbage and must be ignored
    by the caller.
    """
    B, C = tokens.shape

    def body(cache, t):
        live = t < n_tokens
        logits, score, new_cache = serve_step(
            cfg, params, cache, tokens[:, t][:, None], positions + t,
            use_window=use_window, impl=impl)

        def sel(n, o):
            return jnp.where(live.reshape((B,) + (1,) * (n.ndim - 1)), n, o)

        cache = jax.tree_util.tree_map(sel, new_cache, cache)
        return cache, (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                       score.astype(jnp.float32))

    cache, (toks_out, scores) = jax.lax.scan(body, cache, jnp.arange(C))
    return cache, toks_out.T, scores.T


def prefill(cfg: ModelConfig, params, cache, tokens, *, use_window=True,
            impl: str = "auto"):
    """Sequential prefill via serve_step (simple and cache-exact; the batch
    engine amortizes it across requests).  tokens: [B, S0].

    Only the LAST token's logits are observable, so the scan carries the
    cache alone — the old per-token [B, vocab] logits carry forced a
    vocab-sized copy through every scan iteration and kept S0−1 dead
    lm_head matmuls live.  The final step runs outside the scan and
    produces the fp32 logits that tests/test_decode_consistency.py pins
    against the parallel forward (token-by-token MoE dispatch included)."""
    B, S0 = tokens.shape

    def body(cache, t):
        _, _, cache = serve_step(
            cfg, params, cache, tokens[:, t][:, None],
            jnp.full((B,), t, jnp.int32), use_window=use_window, impl=impl)
        return cache, None

    cache, _ = jax.lax.scan(body, cache, jnp.arange(S0 - 1))
    logits, _, cache = serve_step(
        cfg, params, cache, tokens[:, S0 - 1][:, None],
        jnp.full((B,), S0 - 1, jnp.int32), use_window=use_window, impl=impl)
    return cache, logits.astype(jnp.float32)
