"""The training path's own instrumentation: the host spans ``coda.fit``
writes into a profiler trace, the device scopes every layer leaves in the
compiled window's ``op_name`` metadata, and the recompile count."""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs.base import mlp_config
from repro.core import coda, schedules

MCFG = mlp_config(n_features=16, d=32)
K, I, B = 2, 2, 8


def _batch(key, lead):
    ky, kx = jax.random.split(key)
    y = (jax.random.uniform(ky, lead) < 0.7).astype(jnp.float32)
    x = jax.random.normal(kx, lead + (16,)) + 0.3 * (y[..., None] * 2 - 1)
    return {"features": x, "labels": y}


def _fit(ccfg, executor="vmap", **kw):
    sched = schedules.ScheduleConfig(n_workers=K, eta0=0.1, T0=4 * I, I0=I)
    return coda.fit(jax.random.PRNGKey(0), MCFG, ccfg, sched, 1,
                    sample_window=lambda k, i: _batch(k, (i, K, B)),
                    sample_alpha_batch=lambda k, m: _batch(k, (K, m)),
                    executor=executor, **kw)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in plane.lines for e in line.events
            if e.name in tracing.HOST_SPANS]


def test_fit_writes_one_window_span_per_window(tmp_path):
    """Four windows with an eval and a checkpoint every second one: one
    ``coda.window`` each, numbered by the global window counter, holding
    its draw, dispatch, sync (and eval, checkpoint) spans; the stage end
    after the last."""
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7)
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = _fit(ccfg, eval_every=2, eval_fn=lambda st: 0.5,
                   ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    spans = _host_spans(str(tmp_path / "trace"))
    windows = sorted((s for s in spans if s[0] == tracing.WINDOW),
                     key=lambda s: s[1])
    assert [int(s[3]["step_num"]) for s in windows] == [0, 1, 2, 3]
    inner = {tracing.SAMPLE, tracing.DISPATCH, tracing.SYNC, tracing.EVAL,
             tracing.CKPT}
    for gw, (_, t0, t1, _) in enumerate(windows):
        held = sorted(n for n, s, e, _ in spans
                      if n in inner and t0 <= s and e <= t1)
        want = [tracing.DISPATCH, tracing.SAMPLE, tracing.SYNC]
        if gw % 2:
            want = sorted(want + [tracing.CKPT, tracing.EVAL])
        assert held == want, (gw, held)
    assert sum(n in inner for n, *_ in spans) == 3 * 4 + 2 * 2
    stage, = [s for s in spans if s[0] == tracing.STAGE_END]
    assert stage[1] >= windows[-1][2]
    assert res.recompiles == 0


def test_recompiles_counts_a_window_program_compiled_again():
    """An executor that builds a new jitted window on every call compiles
    every window after the first: each one counts."""
    from jax.experimental.compilation_cache import compilation_cache

    ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7)

    class FreshJit(coda.VmapExecutor):
        def window_step(self, state, wb, eta, *, faults=None):
            return jax.jit(lambda st, b, e: coda.window_step(
                MCFG, ccfg, st, b, e))(state, wb, eta)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        res = _fit(ccfg, executor=FreshJit(MCFG, ccfg))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert res.recompiles >= 3


def _scopes(hlo_text: str) -> set:
    """Every scope some op's ``op_name`` path passes through."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        parts = name.split("/")
        out |= {tracing.scope_of("/".join(parts[:k + 1]))
                for k in range(len(parts))}
    return out


@pytest.mark.parametrize("algorithm", ["coda", "codasca"])
def test_vmap_programs_carry_every_scope(algorithm):
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7, algorithm=algorithm)
    key = jax.random.PRNGKey(1)
    st = coda.init_state(key, MCFG, ccfg)
    exe = coda.make_executor(MCFG, ccfg, "vmap", donate=False)
    window = exe._wstep.lower(st, _batch(key, (I, K, B)),
                              jnp.float32(0.1)).compile().as_text()
    stage = exe._send.lower(st, _batch(key, (K, B))).compile().as_text()
    assert _scopes(window) >= set(tracing.SCOPES) - {"stage_end"}
    assert "stage_end" in _scopes(stage)


def test_shard_map_programs_carry_every_scope():
    """The sharded window (plain and the overlapped pair) and the stage
    program on two forced host devices.  CODASCA's window body is
    ``codasca.run_window`` on both executors, checked through the vmap
    oracle above."""
    script = textwrap.dedent("""
        import os, re
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        from repro import tracing
        from repro.configs.base import mlp_config
        from repro.core import coda

        mcfg = mlp_config(n_features=16, d=32)
        mesh = jax.make_mesh((2, 1), ("data", "model"))
        key = jax.random.PRNGKey(1)
        y = (jax.random.uniform(key, (4, 2, 8)) < 0.7).astype(jnp.float32)
        x = jax.random.normal(key, (4, 2, 8, 16))
        eta = jnp.float32(0.1)
        def scopes(t):
            out = set()
            for n in re.findall(r'op_name="([^"]*)"', t):
                p = n.split("/")
                out |= {tracing.scope_of("/".join(p[:k + 1]))
                        for k in range(len(p))}
            return out
        layers = set(tracing.SCOPES) - {"stage_end"}
        for kw in ({}, {"overlap_chunks": 2}):
            ccfg = coda.CoDAConfig(n_workers=2, p_pos=0.7, **kw)
            exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh,
                                     donate=False)
            st = exe.place(coda.init_state(key, mcfg, ccfg))
            if kw.get("overlap_chunks"):
                wb = {"features": x.reshape(2, 2, 2, 8, 16),
                      "labels": y.reshape(2, 2, 2, 8)}
                fn = exe.window_pair_fn(st, wb)
            else:
                wb = {"features": x, "labels": y}
                fn = exe.window_fn(st, wb)
            got = scopes(fn.lower(st, wb, eta).compile().as_text())
            assert got >= layers, (kw, layers - got)
        ab = {"features": x[0], "labels": y[0]}
        stage = exe.stage_fn(st, ab).lower(st, ab).compile().as_text()
        assert "stage_end" in scopes(stage)
        print("ALL OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ALL OK" in r.stdout, r.stdout[-2000:]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(body)/window/while/body/closed_call/local_step/update/"
     "jit(prox_update)/prox_update/pallas_call", "update"),
    ("jit(body)/window/while/body/closed_call/local_step/"
     "vmap(transpose(jvp(model)))/conv_general_dilated", "model"),
    ("local_step/vmap(jvp(auc_loss))/jit(auc_loss)/auc_loss/pallas_call",
     "auc_loss"),
    ("jit(body)/window/average/psum", "average"),
    ("jit(f)/jit(update)/mul", "other"),
    ("st['params']['w']", "other"),
    ("jit(body)/window/while/body/closed_call/local_step/"
     "vmap(transpose(jvp(model)))/while/body/checkpoint/attention/dot_general",
     "attention"),
    ("jit(body)/window/while/body/closed_call/local_step/vmap(jvp(model))/"
     "while/body/checkpoint/moe/jit(grouped_matmul)/pallas_call", "moe"),
    ("jit(body)/window/while/body/closed_call/local_step/vmap(jvp(model))/"
     "while/body/closed_call/moe/cond/branch_0_fun/vmap(moe_full_bound)/"
     "jit(_take)", "moe_full_bound"),
    ("checkpoint/moe/cond/branch_0_fun/vmap(moe_full_bound)/"
     "transpose(jvp())/add", "moe_full_bound"),
])
def test_scope_of(op_name, scope):
    assert tracing.scope_of(op_name) == scope


def test_moe_mla_program_carries_model_scopes():
    """A latent-attention MoE model's CoDA window splits the model's ops
    into the ``attention`` and ``moe`` scopes, inside ``model``; the model
    scopes stay out of ``SCOPES``, which every model's window carries."""
    from repro.configs import get_smoke_config

    assert not set(tracing.MODEL_SCOPES) & set(tracing.SCOPES)
    mcfg = get_smoke_config("moonlight-16b-a3b")
    ccfg = coda.CoDAConfig(n_workers=1, p_pos=0.7)
    key = jax.random.PRNGKey(2)
    st = coda.init_state(key, mcfg, ccfg)
    exe = coda.make_executor(mcfg, ccfg, "vmap", donate=False)
    wb = {"tokens": jax.random.randint(key, (1, 1, 2, 8), 0, mcfg.vocab_size),
          "labels": jnp.asarray([[[1.0, 0.0]]])}
    window = exe._wstep.lower(st, wb, jnp.float32(0.1)).compile().as_text()
    assert {"model", "attention", "moe"} <= _scopes(window)


def test_held_share_program_carries_the_full_bound_scope():
    """A held expert share whose tight row bound is tighter than the full
    one: the window's fallback to the full bound (forward and backward)
    sits under ``moe_full_bound``, inside ``moe``."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import moe

    mcfg = get_smoke_config("moonlight-16b-a3b")
    mcfg = dataclasses.replace(mcfg, moe=dataclasses.replace(
        mcfg.moe, n_experts=8, n_held=2, held_offset=2))
    assert moe.tight_rows(mcfg, 16) < moe.held_rows(mcfg, 16)
    ccfg = coda.CoDAConfig(n_workers=1, p_pos=0.7)
    key = jax.random.PRNGKey(2)
    st = coda.init_state(key, mcfg, ccfg)
    exe = coda.make_executor(mcfg, ccfg, "vmap", donate=False)
    wb = {"tokens": jax.random.randint(key, (1, 1, 2, 8), 0, mcfg.vocab_size),
          "labels": jnp.asarray([[[1.0, 0.0]]])}
    window = exe._wstep.lower(st, wb, jnp.float32(0.1)).compile().as_text()
    assert {"model", "moe", tracing.FULL_BOUND} <= _scopes(window)
