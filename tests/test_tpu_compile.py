"""The training path's Pallas kernels compile for a TPU v5e chip.

Off-TPU, ``ops.dispatch("auto")`` routes every kernel to ``kernels/ref.py``
and the interpret-mode tests never reach the Mosaic compiler, so a block
layout the chip refuses would pass every other test.  Here each kernel is
compiled at the training path's real sizes for one chip of a *described*
``v5e:2x2`` topology (nothing runs) and the compiled program must hold the
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every pytest-xdist worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.auc_loss import auc_loss
from repro.kernels.opt_update import opt_update
from repro.kernels.prox_update import prox_update

# one 3x3x256x256 conv of ResNet-50's stage 3 (the largest kernel leaf)
RESNET50_CONV_LEAF = 2_359_296


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T", [32, 4096])
def test_auc_loss_compiles_for_v5e(one_chip, T):
    s = lambda shape: _spec(one_chip, shape)
    txt = _compiled_text(lambda h, y, a, b, al: auc_loss(h, y, a, b, al, 0.7),
                         s((T,)), s((T,)), s(()), s(()), s(()))
    assert "tpu_custom_call" in txt


def test_auc_loss_compiles_vmapped_over_workers(one_chip):
    """The CoDA local step vmaps the loss over the K workers, which turns
    every block into a batched (squeezed, ...) block."""
    K, T = 4, 32
    s = lambda shape: _spec(one_chip, shape)
    loss = jax.vmap(lambda h, y, a, b, al: auc_loss(h, y, a, b, al, 0.7))
    txt = _compiled_text(loss, s((K, T)), s((K, T)), s((K,)), s((K,)),
                         s((K,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("N,dtype", [
    (RESNET50_CONV_LEAF, jnp.float32),
    (64, jnp.float32),      # a 64-wide GroupNorm scale
    (64, jnp.bfloat16),     # the same leaf under param_dtype=bf16
])
def test_prox_update_compiles_for_v5e(one_chip, N, dtype):
    s = lambda: _spec(one_chip, (N,), dtype)
    txt = _compiled_text(lambda v, g, v0, eta: prox_update(v, g, v0, eta, 0.5),
                         s(), s(), s(), _spec(one_chip, ()))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mode,buf_dtype", [
    ("momentum", jnp.bfloat16),   # --opt-dtype bf16: stochastic-rounded
    ("precond", jnp.float32),     # the SM3 accumulator cover
])
def test_opt_update_compiles_for_v5e(one_chip, mode, buf_dtype):
    N = 1000
    s = lambda dtype=jnp.float32: _spec(one_chip, (N,), dtype)
    txt = _compiled_text(
        lambda v, g, v0, buf, eta: opt_update(v, g, v0, buf, eta, 0.5, 0.9,
                                              jnp.uint32(7), mode=mode),
        s(), s(), s(), s(buf_dtype), _spec(one_chip, ()))
    assert "tpu_custom_call" in txt
