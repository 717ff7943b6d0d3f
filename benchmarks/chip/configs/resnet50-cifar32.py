"""ResNet-50 on 32x32 images: the program's configuration, weights in the
program's parameter layout, a plain float32 reference forward, and the
model FLOPs of one sample.

The reference reads nothing of the program.  It follows the architecture
as ``resnet50-cifar32.json`` states it: bottleneck blocks (1x1, 3x3, 1x1)
with the stride on the 3x3 conv and on the 1x1 projection of each stage's
first block, GroupNorm(32) after every conv, a 3x3 stride-1 stem, global
average pooling, and a sigmoid of one linear score.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration.  The program
    chooses its stage layout by name, so the stated stages must be one of
    its two layouts."""
    from repro.configs import get_config, get_smoke_config
    from repro.configs.resnet50 import RESNET50_STAGES, RESNET_TINY_STAGES

    stages = tuple(tuple(s) for s in cfg["stages"])
    if stages == RESNET50_STAGES:
        return get_config("resnet50")
    if stages == RESNET_TINY_STAGES:
        return get_smoke_config("resnet50")
    raise ValueError(f"the program has no ResNet with stages {stages}")


def inputs(cfg: dict) -> dict:
    return {"kind": "images", "image_hw": cfg["image_hw"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _conv_w(key, k, cin, cout):
    return jax.random.normal(key, (k, k, cin, cout), jnp.float32) * (
        2.0 / (k * k * cin)) ** 0.5


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def make_weights(key, cfg: dict):
    """He-normal convs, unit GroupNorm scales, a N(0, 1/d) score head: one
    worker's parameters in the program's tree layout."""
    stages = cfg["stages"]
    n_keys = 2 + sum(n for n, _ in stages) * 4
    ks = iter(jax.random.split(key, n_keys))
    c0 = cfg["stem"]["channels"]
    p = {"stem": {"w": _conv_w(next(ks), cfg["stem"]["kernel"], 3, c0),
                  "gn": _gn(c0)},
         "stages": []}
    cin = c0
    for n_blocks, cout in stages:
        mid = cout // 4
        blocks = []
        for b in range(n_blocks):
            blk = {"w1": _conv_w(next(ks), 1, cin, mid), "gn1": _gn(mid),
                   "w2": _conv_w(next(ks), 3, mid, mid), "gn2": _gn(mid),
                   "w3": _conv_w(next(ks), 1, mid, cout), "gn3": _gn(cout)}
            if b == 0 and cin != cout:
                blk["wproj"] = _conv_w(next(ks), 1, cin, cout)
            blocks.append(blk)
            cin = cout
        p["stages"].append(blocks)
    d = stages[-1][1]
    head = {"w": jax.random.normal(next(ks), (d, 1), jnp.float32) * d ** -0.5,
            "b": jnp.zeros((1,), jnp.float32)}
    return {"backbone": p, "score_head": head}


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, p, groups, eps):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(x.shape)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def score(params, batch: dict, cfg: dict, dtype=jnp.float32):
    """h(w; x) in (0, 1) for every image of ``batch["images"]`` [B, hw*hw, 3],
    computed in ``dtype``."""
    groups, eps = cfg["norm"]["groups"], cfg["norm"]["eps"]
    gn = lambda x, p: _group_norm(x, p, groups, eps)
    hw = cfg["image_hw"]
    x = batch["images"].astype(dtype).reshape(-1, hw, hw, cfg["channels"])
    bb = params["backbone"]
    x = jax.nn.relu(gn(_conv(x, bb["stem"]["w"], cfg["stem"]["stride"]),
                       bb["stem"]["gn"]))
    for si, blocks in enumerate(bb["stages"]):
        for bi, blk in enumerate(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            h = jax.nn.relu(gn(_conv(x, blk["w1"], 1), blk["gn1"]))
            h = jax.nn.relu(gn(_conv(h, blk["w2"], stride), blk["gn2"]))
            h = gn(_conv(h, blk["w3"], 1), blk["gn3"])
            short = _conv(x, blk["wproj"], stride) if "wproj" in blk else x
            x = jax.nn.relu(h + short)
    pooled = jnp.mean(x, axis=(1, 2))
    head = params["score_head"]
    logit = pooled @ head["w"][:, 0].astype(dtype) + head["b"][0].astype(dtype)
    return jax.nn.sigmoid(logit.astype(jnp.float32))


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------
def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward plus backward FLOPs of one image: 2 per multiply-add of every
    conv and of the score head, times 3 (the backward pass takes the
    gradients of inputs and of weights).  Norms, activations, pooling and
    the loss add under 1% and are not counted."""
    hw = cfg["image_hw"]
    c0, k0 = cfg["stem"]["channels"], cfg["stem"]["kernel"]
    macs = hw * hw * k0 * k0 * cfg["channels"] * c0
    cin = c0
    for si, (n_blocks, cout) in enumerate(cfg["stages"]):
        mid = cout // 4
        for b in range(n_blocks):
            stride = 2 if (b == 0 and si > 0) else 1
            hw_out = -(-hw // stride)
            macs += hw * hw * cin * mid               # 1x1 at the input size
            macs += hw_out * hw_out * 9 * mid * mid   # 3x3, strided
            macs += hw_out * hw_out * mid * cout      # 1x1
            if b == 0 and cin != cout:
                macs += hw_out * hw_out * cin * cout  # projection
            hw, cin = hw_out, cout
    macs += cin  # score head
    return 3.0 * 2.0 * macs
