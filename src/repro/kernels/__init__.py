"""Pallas TPU kernels for the compute hot-spots under CoDA:

  * flash_attention — dominant FLOP consumer of every backbone
  * auc_loss        — the paper's fused min-max objective + closed-form grads
  * prox_update     — CoDA's fused proximal local update (3 model copies/step)
  * moe_dispatch    — grouped expert GEMM for sorted dropless MoE dispatch,
                      forward and backward (training, eval and decode)

Each has a pure-jnp oracle in ``ref.py`` and a jit'd dispatcher in ``ops.py``.
"""
from repro.kernels import ops, ref  # noqa: F401
