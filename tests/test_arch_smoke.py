"""Deliverable (f): per assigned-architecture smoke tests on REDUCED
same-family variants (≤2 layers, d_model ≤ 512, ≤4 experts): one forward and
one CoDA train step on CPU, asserting output shapes and no NaNs; plus one
serve_step decode token where the family has a decode path."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core import coda
from repro.models import init_params, score
from repro.serving import decode as D

B, S = 2, 64


def _batch(cfg, lead, key):
    kt, kp = jax.random.split(key)
    out = {}
    if cfg.family == "vlm":
        out["patches"] = jax.random.normal(kp, lead + (cfg.n_patches, cfg.d_model))
        out["tokens"] = jax.random.randint(kt, lead + (S - cfg.n_patches,), 0,
                                           cfg.vocab_size)
    elif cfg.family == "audio":
        out["frames"] = jax.random.normal(kp, lead + (S, cfg.d_model))
        out["tokens"] = jax.random.randint(kt, lead + (S // cfg.decoder_fraction,),
                                           0, cfg.vocab_size)
    elif cfg.family == "cnn":
        out["images"] = jax.random.normal(kp, lead + (1024, 3))
    else:
        out["tokens"] = jax.random.randint(kt, lead + (S,), 0, cfg.vocab_size)
    out["labels"] = (jax.random.uniform(kp, lead) < 0.7).astype(jnp.float32)
    return out


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_coda_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 or cfg.family == "cnn"
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    batch = _batch(cfg, (B,), key)
    h, aux = score(cfg, params, {k: v for k, v in batch.items() if k != "labels"})
    assert h.shape == (B,)
    assert bool(jnp.all(jnp.isfinite(h))) and bool(jnp.all((h >= 0) & (h <= 1)))

    K = 2
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7)
    state = coda.init_state(key, cfg, ccfg)
    wb = _batch(cfg, (1, K, B), key)
    state, losses = coda.window_step(cfg, ccfg, state, wb, 0.05)
    for leaf in jax.tree_util.tree_leaves(state):
        assert bool(jnp.all(jnp.isfinite(leaf))), arch
    assert bool(jnp.all(jnp.isfinite(losses)))


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS if a != "resnet50"])
def test_serve_step_one_token(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    if cfg.kv_lora_rank:
        # latent attention trains and scores; its latent cache does not
        # exist yet, and decode says so
        with pytest.raises(NotImplementedError, match="latent"):
            D.init_cache(cfg, B, 32, dtype=jnp.float32)
        with pytest.raises(NotImplementedError, match="latent"):
            D.serve_step(cfg, params, None, jnp.zeros((B, 1), jnp.int32),
                         jnp.zeros((B,), jnp.int32))
        return
    cache = D.init_cache(cfg, B, 32, use_window=True, dtype=jnp.float32)
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    pos = jnp.zeros((B,), jnp.int32)
    logits, score_logit, cache2 = D.serve_step(cfg, params, cache, tok, pos)
    assert logits.shape == (B, cfg.vocab_size)
    assert score_logit.shape == (B,)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # a second token must also work against the updated cache
    logits2, _, _ = D.serve_step(cfg, params, cache2, tok, pos + 1)
    assert bool(jnp.all(jnp.isfinite(logits2)))
