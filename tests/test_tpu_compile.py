"""The training path's Pallas kernels compile for a TPU v5e chip.

Off-TPU, ``ops.dispatch("auto")`` routes every kernel to ``kernels/ref.py``
and the interpret-mode tests never reach the Mosaic compiler, so a block
layout the chip refuses would pass every other test.  Here each kernel is
compiled at the training path's real sizes for one chip of a *described*
``v5e:2x2`` topology (nothing runs) and the compiled program must hold the
Mosaic kernel (``tpu_custom_call``), named after the kernel: the trace
names each device operation by its HLO instruction, and the benchmark's
readers find a kernel by that name.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every pytest-xdist worker imports this file.
"""
import importlib.util
import math
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.auc_loss import auc_loss
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_dispatch import grouped_matmul
from repro.kernels.opt_update import opt_update
from repro.kernels.prox_update import prox_update, tiled_geometry

CHIP_BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

# one 3x3x256x256 conv of ResNet-50's stage 3 (the largest kernel leaf)
RESNET50_CONV_LEAF = 2_359_296
# the scoped VMEM a v5e kernel may use unless it asks for more
V5E_SCOPED_VMEM = 16 << 20


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


def _kernel_calls(txt: str) -> list[str]:
    """The HLO instruction names of the compiled program's Mosaic calls."""
    return re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                      r"\"tpu_custom_call\"", txt, re.M)


def _assert_named(txt: str, name: str) -> None:
    """Every Mosaic call is ``<name>.<n>``, whatever transforms (vmap, jvp)
    the kernel was called under."""
    calls = _kernel_calls(txt)
    assert calls and {re.sub(r"(\.\d+)+$", "", c) for c in calls} == {name}, \
        calls


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T", [32, 4096])
def test_auc_loss_compiles_for_v5e(one_chip, T):
    s = lambda shape: _spec(one_chip, shape)
    txt = _compiled_text(lambda h, y, a, b, al: auc_loss(h, y, a, b, al, 0.7),
                         s((T,)), s((T,)), s(()), s(()), s(()))
    _assert_named(txt, "auc_loss")


def test_auc_loss_compiles_vmapped_over_workers(one_chip):
    """The CoDA local step vmaps the loss over the K workers, which turns
    every block into a batched (squeezed, ...) block."""
    K, T = 4, 32
    s = lambda shape: _spec(one_chip, shape)
    loss = jax.vmap(lambda h, y, a, b, al: auc_loss(h, y, a, b, al, 0.7))
    txt = _compiled_text(loss, s((K, T)), s((K, T)), s((K,)), s((K,)),
                         s((K,)))
    _assert_named(txt, "auc_loss")


@pytest.mark.parametrize("N,dtype", [
    (RESNET50_CONV_LEAF, jnp.float32),
    (64, jnp.float32),      # a 64-wide GroupNorm scale
    (64, jnp.bfloat16),     # the same leaf under param_dtype=bf16
])
def test_prox_update_compiles_for_v5e(one_chip, N, dtype):
    s = lambda: _spec(one_chip, (N,), dtype)
    txt = _compiled_text(lambda v, g, v0, eta: prox_update(v, g, v0, eta, 0.5),
                         s(), s(), s(), _spec(one_chip, ()))
    _assert_named(txt, "prox_update")


def _ops_on_leaf_size(txt: str, size: int, opcodes) -> list[str]:
    """Instructions of ``opcodes`` whose result holds ``size`` elements."""
    return [f"{op} {shape}" for shape, dims, op in re.findall(
        r"^\s*(?:ROOT )?%\S+ = (\w+\[([\d,]*)\])\S* ([\w-]+)\(", txt, re.M)
        if op in opcodes
        and math.prod(int(d) for d in dims.split(",") if d) == size]


@pytest.mark.parametrize("shape,vmapped", [
    ((100352, 2048), False),       # StableLM-2's embedding
    ((6, 2048, 5632), False),      # six stacked SwiGLU projections
    ((4, 3, 3, 256, 256), True),   # a ResNet-50 conv, vmapped over K = 4
])
def test_prox_update_tree_keeps_the_leafs_layout(one_chip, monkeypatch,
                                                 shape, vmapped):
    """Through ``ops.prox_update_tree``'s kernel path a leaf is updated in
    its own tiled layout: one Mosaic call, and no copy, reshape or
    transpose of the leaf's size around it (the collapse of leading axes
    into rows is a bitcast), nor of its parameters: the kernel writes in
    place, so with v donated, as the window's loop carries it, no copy of
    v outlives the call.  Its double-buffered blocks fit the scoped VMEM."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    upd = lambda v, g, v0, eta: ops.prox_update_tree(v, g, v0, eta, 0.5)
    if vmapped:
        upd = jax.vmap(upd, in_axes=(0, 0, 0, None))
    s = lambda: _spec(one_chip, shape)
    txt = jax.jit(upd, donate_argnums=0).lower(
        s(), s(), s(), _spec(one_chip, ())).compile().as_text()
    _assert_named(txt, "prox_update")
    assert len(_kernel_calls(txt)) == 1
    assert _ops_on_leaf_size(txt, math.prod(shape), ("copy", "reshape", "transpose",
                                         "copy-start", "pad", "slice")) == []
    leaf = shape[1:] if vmapped else shape
    assert tiled_geometry(leaf)["vmem_bytes"] <= V5E_SCOPED_VMEM


def test_prox_update_roofline_finds_the_update_kernel(one_chip):
    """``prox_update_roofline`` sums the trace's ops whose name matches its
    pattern at the start: it must match the kernel's instruction inside the
    training step's ``update`` scope, and no other."""
    sys.path.insert(0, str(CHIP_BENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            "prox_update_roofline",
            CHIP_BENCH / "metrics" / "prox_update_roofline.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(str(CHIP_BENCH))

    def step(v, g, v0, eta):
        with jax.named_scope("local_step"), jax.named_scope("update"):
            return prox_update(v, g, v0, eta, 0.5) * 2.0

    s = lambda: _spec(one_chip, (RESNET50_CONV_LEAF,))
    txt = _compiled_text(step, s(), s(), s(), _spec(one_chip, ()))
    names = re.findall(r"^\s*(?:ROOT )?%(\S+) = ", txt, re.M)
    hits = [n for n in names if re.match(reader.KERNEL, n)]
    assert hits and hits == _kernel_calls(txt)


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
    _assert_named(txt, "opt_update")


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _spec(one_chip, (1, 512, 4, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 512, 2, 128), jnp.bfloat16)
    txt = _compiled_text(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    _assert_named(txt, "flash_attention")


def test_grouped_matmul_compiles_for_v5e(one_chip):
    x = _spec(one_chip, (256, 128), jnp.bfloat16)
    w = _spec(one_chip, (2, 128, 128), jnp.bfloat16)
    sizes = _spec(one_chip, (2,), jnp.int32)
    txt = _compiled_text(grouped_matmul, x, w, sizes)
    _assert_named(txt, "grouped_matmul")


def test_grouped_matmul_backward_compiles_at_moonlight_widths(one_chip):
    """The dropless MoE's three expert products and their backward at the
    published widths (hidden 2048, expert 1408, 8 held experts) on fewer
    rows: the forward/dx kernel and the dw kernel compile within the
    VMEM they ask for, the transposed-LHS contraction of dw included, and
    ``grouped_matmul_roofline``'s pattern finds both and nothing else."""
    sys.path.insert(0, str(CHIP_BENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            "grouped_matmul_roofline",
            CHIP_BENCH / "metrics" / "grouped_matmul_roofline.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(str(CHIP_BENCH))

    def expert_mlp(x, wg, wu, wd, sizes):
        h = grouped_matmul(x, wg, sizes)
        u = grouped_matmul(x, wu, sizes)
        return jnp.sum(grouped_matmul(jax.nn.silu(h) * u, wd, sizes))

    d, ff, E = 2048, 1408, 8
    txt = _compiled_text(
        jax.grad(expert_mlp, argnums=(0, 1, 2, 3)),
        _spec(one_chip, (1024, d)), _spec(one_chip, (E, d, ff)),
        _spec(one_chip, (E, d, ff)), _spec(one_chip, (E, ff, d)),
        _spec(one_chip, (E,), jnp.int32))
    calls = _kernel_calls(txt)
    assert sorted({re.sub(r"(\.\d+)+$", "", c) for c in calls}) == \
        ["grouped_matmul", "grouped_matmul_wgrad"], calls
    names = re.findall(r"^\s*(?:ROOT )?%(\S+) = ", txt, re.M)
    assert sorted(n for n in names if re.match(reader.KERNEL, n)) == \
        sorted(calls)


def test_held_share_dispatch_compiles_at_moonlight_widths(one_chip,
                                                          monkeypatch):
    """One MoE layer of Moonlight's expert share (8 of 64 experts held, 6
    per token, published widths) on 1,024 tokens, differentiated under the
    layer's remat and the executor's worker vmap, with the kernels the chip
    runs: the tight and the full row bound sit in one conditional forward
    and one backward; each bound's expert products run 3 times forward and
    6 times backward (3 of them the backward's own forward), as the single
    bound's did; the remat forward's copy is gone."""
    import dataclasses
    from collections import Counter

    from repro.configs import get_config
    from repro.kernels.moe_dispatch import launch_geometry
    from repro.models import moe

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=8, held_offset=8))
    T, d = 1024, cfg.d_model
    R, R_t = moe.held_rows(cfg, T), moe.tight_rows(cfg, T)
    assert (R, R_t) == (6144, 1536)
    pad = lambda n: launch_geometry(n, d, 8, cfg.d_ff)["Np"]

    layer = jax.checkpoint(
        lambda p, x: moe.apply_moe(cfg, p, x, train=True)[0])
    grad = jax.vmap(jax.value_and_grad(lambda p, x: jnp.sum(layer(p, x)),
                                       argnums=(0, 1)))
    p = jax.eval_shape(lambda k: moe.init_moe(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    p = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, (1,) + a.shape, a.dtype), p)
    txt = _compiled_text(grad, p, _spec(one_chip, (1, 1, T, d)))
    assert len(re.findall(r" conditional\(", txt)) == 2
    rows = Counter(re.findall(
        r"%grouped_matmul\.\d+ = \w+\[(?:1,)?(\d+),", txt))
    assert rows == {str(pad(R_t)): 9, str(pad(R)): 9}, rows
    assert len(re.findall(r"%grouped_matmul_wgrad\.\d+ = ", txt)) == 6
