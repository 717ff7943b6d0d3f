"""Configuration dataclasses for every architecture the framework supports.

A ``ModelConfig`` fully determines parameter shapes and the forward pass of
the scoring network ``h(w; x)`` used by CoDA, as well as the autoregressive
``serve_step`` used by the decode input shapes.  One module per assigned
architecture lives next to this file; each exports ``CONFIG`` (the exact
pool numbers) and ``smoke_config()`` (a reduced same-family variant for CPU
tests: <=2 layers, d_model <= 512, <= 4 experts).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration.

    ``dispatch`` selects the dispatch implementation, in training and in
    eval/decode alike:
      * "sorted"   — dropless sort-based dispatch: the rows routed to the
                     held experts, sorted by expert, + ragged grouped GEMM
                     over expert segments (models/moe.py).
      * "capacity" — the padded scatter dispatch through an [E, C, d]
                     buffer: the capacity-factor bound in training (over-
                     capacity tokens are dropped), the static dropless bound
                     C = T at eval (kept for A/B and as the oracle the
                     sorted path is tested against).

    ``scoring``: "softmax" (top-k of a softmax, Switch auxiliary loss) or
    "sigmoid" (DeepSeek-V3's ``noaux_tc`` with one group: top-k of sigmoid
    scores plus a selection-bias leaf, gate weights the unbiased scores of
    the chosen experts; no auxiliary loss).  Gate weights are renormalised
    over the chosen k and scaled by ``routed_scale``.

    The held share: this layer holds experts ``[held_offset, held_offset +
    n_held)`` of ``n_experts`` (``n_held`` 0: all of them), routes every
    token over all ``n_experts`` and computes only its own experts' part
    (expert parallelism without the exchange).
    """

    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # An always-on MLP of width ``dense_d_ff`` next to the experts: Arctic's
    # dense residual, or DeepSeek's shared experts (their count x expert width).
    dense_residual: bool = False
    dense_d_ff: int = 0
    dispatch: str = "sorted"
    scoring: str = "softmax"
    routed_scale: float = 1.0
    held_offset: int = 0
    n_held: int = 0

    def __post_init__(self):
        if self.dispatch not in ("sorted", "capacity"):
            raise ValueError(
                f"unknown moe dispatch {self.dispatch!r} (want sorted | capacity)")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe scoring {self.scoring!r} (want softmax | sigmoid)")
        if self.n_held == 0:
            object.__setattr__(self, "n_held", self.n_experts)
        if not 0 <= self.held_offset <= self.n_experts - self.n_held:
            raise ValueError(f"held experts [{self.held_offset}, "
                             f"{self.held_offset + self.n_held}) outside "
                             f"the {self.n_experts} routed experts")
        if self.n_held < self.n_experts and self.dispatch != "sorted":
            raise ValueError("a held share of the experts needs the sorted "
                             "dispatch")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    ``family`` is one of: dense | moe | vlm | audio | hybrid | ssm.
    """

    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    source: str = ""  # citation for the config numbers

    # --- attention details -------------------------------------------------
    head_dim: int = 0  # 0 => d_model // n_heads
    rope: str = "1d"  # "1d" | "2d-partial" (ChatGLM) | "partial" | "none"
    rope_fraction: float = 1.0  # fraction of head_dim that is rotated
    rope_base: float = 10000.0
    qkv_bias: bool = False
    # Multi-head latent attention (DeepSeek-V2/V3) where ``kv_lora_rank`` > 0:
    # keys and values come up from one RMS-normed latent of that width, each
    # head's query and key are ``head_dim`` wide, their last
    # ``qk_rope_head_dim`` rotated (the key's rotated part is one head shared
    # by all), and values are ``v_head_dim`` wide.  No query compression.
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    act: str = "swiglu"  # "swiglu" | "gelu"
    # Sliding-window attention.  ``window`` is the size used when a layer is
    # a window layer; ``window_layers`` says which layers use it ("none",
    # "all", "all_but_global").  Dense archs get window="optional": full
    # attention by default, window only for the long_500k shape.
    window: int = 4096
    window_mode: str = "none"  # "none" | "all_but_global" | "optional"
    global_attn_every: int = 0  # hybrid: every Nth layer uses global attn

    # --- mixture of experts -------------------------------------------------
    moe: MoEConfig | None = None
    # Leading dense layers ahead of the MoE stack (DeepSeek's
    # ``first_k_dense_replace``), with an MLP of their own width; they count
    # in ``n_layers``.
    dense_layers: int = 0
    dense_layers_d_ff: int = 0

    # --- state-space / hybrid ----------------------------------------------
    ssm_state: int = 0  # N for mamba-style SSM (hymba)
    ssm_expand: int = 2
    ssm_conv: int = 4
    slstm_every: int = 0  # xLSTM: every Nth block is an sLSTM block

    # --- encoder-decoder (audio) ---------------------------------------------
    encoder_layers: int = 0
    is_encoder_decoder: bool = False
    decoder_fraction: int = 4  # decoder seq = seq_len // decoder_fraction

    # --- multimodal stubs -----------------------------------------------------
    n_patches: int = 0  # VLM: number of stubbed vision-patch embeddings

    # --- misc -----------------------------------------------------------------
    tie_embeddings: bool = False
    n_features: int = 0  # mlp family: flat input feature dim

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert self.family in ("dense", "moe", "vlm", "audio", "hybrid", "ssm",
                               "cnn", "mlp")

    # -- derived quantities used by the roofline -----------------------------
    def param_count(self) -> int:
        """Total parameter count N (per worker replica)."""
        from repro.models import model as _model

        return _model.count_params(self)

    def active_param_count(self) -> int:
        """Active (per-token) parameter count: MoE counts only top-k experts."""
        from repro.models import model as _model

        return _model.count_params(self, active_only=True)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One of the four assigned global input shapes."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


def mlp_config(n_features: int = 64, d: int = 128, n_layers: int = 2) -> ModelConfig:
    """Tiny MLP scorer for fast CPU convergence experiments (the paper's
    trends — linear speedup in K, communication skipping — are model
    agnostic; ResNet50 is available for the faithful variant)."""
    return ModelConfig(name="mlp", family="mlp", n_layers=n_layers, d_model=d,
                       n_heads=1, n_kv_heads=1, d_ff=d, vocab_size=0,
                       rope="none", n_features=n_features)


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def input_specs(
    cfg: ModelConfig,
    shape: ShapeSpec,
    *,
    n_workers: int = 1,
    window_steps: int = 1,
    dtype=jnp.bfloat16,
) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a given shape.

    For training shapes this is the CoDA *window* batch
    ``[window_steps, n_workers, per_worker_batch, ...]``; for decode shapes it
    is the serving request batch (the KV cache itself is produced by
    ``serving.cache_specs``).  No device memory is allocated.
    """
    S = shape.seq_len
    B = shape.global_batch
    if shape.kind in ("train", "prefill"):
        assert B % n_workers == 0, (cfg.name, shape.name, n_workers)
        bw = B // n_workers
        lead: tuple[int, ...] = (window_steps, n_workers, bw)
        specs = {}
        if cfg.family == "vlm":
            n_txt = S - cfg.n_patches
            specs["patches"] = jax.ShapeDtypeStruct(lead + (cfg.n_patches, cfg.d_model), dtype)
            specs["tokens"] = jax.ShapeDtypeStruct(lead + (n_txt,), jnp.int32)
        elif cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(lead + (S, cfg.d_model), dtype)
            specs["tokens"] = jax.ShapeDtypeStruct(lead + (S // cfg.decoder_fraction,), jnp.int32)
        elif cfg.family == "cnn":
            specs["images"] = jax.ShapeDtypeStruct(lead + (S, 3), dtype)  # flattened pixels
        elif cfg.family == "mlp":
            specs["features"] = jax.ShapeDtypeStruct(lead + (cfg.n_features,), dtype)
        else:
            specs["tokens"] = jax.ShapeDtypeStruct(lead + (S,), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct(lead, jnp.float32)
        return specs
    # decode: one new token against a cache of length S
    specs = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "positions": jax.ShapeDtypeStruct((B,), jnp.int32)}
    return specs
