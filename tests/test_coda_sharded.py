"""Sharded-executor tests: vmap-oracle equivalence on 8 forced host devices
(replica + fsdp policies, K=1 / I=1 degenerate cases), int8 compressed
averaging (exactness, error bound, and that the wire payload really is s8),
and communication accounting cross-checked against the all-reduce ops the
compiler emitted.

The mesh-parallel checks run in subprocesses because
``--xla_force_host_platform_device_count`` must be set before jax
initialises its backend, and the parent pytest process has usually already
touched jax by the time this module runs.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, strategies as st

from repro.configs.base import mlp_config
from repro.core import coda, schedules

MCFG = mlp_config(n_features=16, d=32)

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import mlp_config
    from repro.core import coda, schedules

    mcfg = mlp_config(n_features=16, d=32)

    def make_case(K, I, B=8, compress="", seed=0):
        ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7, avg_compress=compress)
        key = jax.random.PRNGKey(seed)
        st0 = coda.init_state(key, mcfg, ccfg)
        ky, kx = jax.random.split(key)
        y = (jax.random.uniform(ky, (I, K, B)) < 0.7).astype(jnp.float32)
        x = jax.random.normal(kx, (I, K, B, 16)) + 0.3 * (y[..., None] * 2 - 1)
        wb = {"features": x, "labels": y}
        ab = {"features": x[0], "labels": y[0]}
        return ccfg, st0, wb, ab

    def assert_trees_close(got, want, tol, label):
        for (p, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                  jax.tree_util.tree_flatten_with_path(want)[0]):
            err = float(jnp.max(jnp.abs(a - b)))
            assert err < tol, (label, jax.tree_util.keystr(p), err)
""")


def _run(script: str, timeout=900):
    r = subprocess.run([sys.executable, "-c", _PRELUDE + textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ALL OK" in r.stdout, r.stdout[-2000:]


# --------------------------------------------------------------------------
# vmap-oracle equivalence on a real 8-device mesh
# --------------------------------------------------------------------------
def test_shard_map_matches_vmap_oracle():
    """window_step + stage_end through shard_map must match the single-device
    oracle to fp32 tolerance: replica (K=8 sharded over 8 devices) and fsdp
    (K=2 over the pod axis) policies, plus the K=1 (PPD-SG) and I=1
    (NP-PPD-SG) degenerate cases."""
    _run("""
    mesh2 = jax.make_mesh((8, 1), ("data", "model"))
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cases = [
        ("replica K=8 I=4", 8, 4, "replica", mesh2, ("data",)),
        ("replica K=1 (PPD-SG)", 1, 3, "replica", mesh2, ()),
        ("replica I=1 (NP-PPD-SG)", 8, 1, "replica", mesh2, ("data",)),
        ("fsdp multi-pod K=2", 2, 3, "fsdp", mesh3, ("pod",)),
    ]
    for label, K, I, policy, mesh, want_wa in cases:
        ccfg, st0, wb, ab = make_case(K, I)
        exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh,
                                 policy=policy, donate=False)
        assert exe.worker_axes == want_wa, (label, exe.worker_axes)
        st1, losses = exe.window_step(exe.place(st0), wb, 0.1)
        st2 = exe.stage_end(st1, ab)
        r1, rl = coda.window_step(mcfg, ccfg, st0, wb, 0.1)
        r2 = coda.stage_end(mcfg, ccfg, r1, ab, resync=False)
        assert losses.shape == (I, K), (label, losses.shape)
        assert_trees_close(st1, r1, 1e-5, label + "/window")
        assert_trees_close(st2, r2, 1e-5, label + "/stage")
        np.testing.assert_allclose(np.asarray(jnp.mean(losses, axis=1)),
                                   np.asarray(rl), atol=1e-5)
        print("OK", label)
    print("ALL OK")
    """)


def test_shard_map_int8_matches_oracle_and_ships_s8():
    """The compressed path must match the vmap oracle's int8 averaging AND
    actually put int8 on the wire: the lowered window HLO contains no fp32
    all-reduce of the model — only the s8 payload all-gather plus the fp32
    per-tensor scales."""
    _run("""
    from repro.analysis import hlo as H
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    K, I = 8, 2
    ccfg, st0, wb, ab = make_case(K, I, compress="int8")
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh, donate=False)
    st1, _ = exe.window_step(exe.place(st0), wb, 0.1)
    r1, _ = coda.window_step(mcfg, ccfg, st0, wb, 0.1)
    assert_trees_close(st1, r1, 1e-5, "int8/window")

    txt = exe.window_fn(st0, wb).lower(st0, wb, jnp.float32(0.1)) \\
             .compile().as_text()
    ops = H.collective_ops(txt)
    assert all(o["op"] == "all-gather" for o in ops), ops
    by_dtype = {}
    for o in ops:
        for dt, b in o["by_dtype"].items():
            by_dtype[dt] = by_dtype.get(dt, 0) + b
    n_elems = sum(l.size // K for l in
                  jax.tree_util.tree_leaves(st0["params"])) + 3
    n_tensors = len(jax.tree_util.tree_leaves(st0["params"])) + 3
    assert by_dtype.get("s8") == K * n_elems, by_dtype        # 1 B/elem wire
    assert by_dtype.get("f32") == K * n_tensors * 4, by_dtype  # scales only
    # gathered bytes / K == what one worker ships == model_bytes(int8)
    assert sum(by_dtype.values()) // K == coda.model_bytes(st0, "int8")
    print("ALL OK")
    """)


# --------------------------------------------------------------------------
# communication accounting vs the compiler
# --------------------------------------------------------------------------
def test_comm_accounting_matches_lowered_hlo():
    """comm_rounds / model_bytes / comm_bytes must agree with the compiled
    artifact: one compiled window = exactly one cross-worker all-reduce whose
    bytes equal model_bytes(state); communicate=False = zero collectives; a
    stage boundary ships one f32 scalar.  Checked over several (T, I)
    schedules."""
    _run("""
    from repro.analysis import hlo as H
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    K, B = 8, 8
    ccfg, st0, _, ab = make_case(K, 1, B=B)
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh, donate=False)

    def window_ops(I, communicate=True):
        wb = {"features": jax.ShapeDtypeStruct((I, K, B, 16), jnp.float32),
              "labels": jax.ShapeDtypeStruct((I, K, B), jnp.float32)}
        sts = jax.eval_shape(lambda s: s, st0)
        txt = exe.window_fn(sts, wb, communicate=communicate).lower(
            sts, wb, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
        return H.collective_ops(txt)

    mb = coda.model_bytes(st0)
    for I in (1, 4, 8):
        ops = window_ops(I)
        ars = [o for o in ops if o["op"] == "all-reduce"]
        assert len(ops) == len(ars) == 1, (I, ops)   # exactly ONE all-reduce
        assert ars[0]["bytes"] == mb, (I, ars[0], mb)
        assert "0,1,2,3,4,5,6,7" in ars[0]["replica_groups"], ars[0]
    assert window_ops(4, communicate=False) == []    # I local steps: silent

    sts = jax.eval_shape(lambda s: s, st0)
    stage_txt = exe.stage_fn(sts, ab).lower(sts, ab).compile().as_text()
    stage_ops = H.collective_ops(stage_txt)
    assert len(stage_ops) == 1 and stage_ops[0]["op"] == "all-reduce"
    assert stage_ops[0]["bytes"] == 4, stage_ops     # one fp32 scalar

    for T0, I0, n_stages in [(6, 1, 2), (8, 4, 2), (30, 8, 3)]:
        sched = schedules.ScheduleConfig(n_workers=K, eta0=0.5, T0=T0, I0=I0)
        sl = schedules.stages(sched, n_stages)
        n_windows = sum(-(-s.T // s.I) for s in sl)
        assert coda.comm_rounds(sl) == n_windows + n_stages
        hlo_total = n_windows * mb + n_stages * 4
        assert hlo_total == coda.comm_bytes(sl, st0), (T0, I0)
    print("ALL OK")
    """)


def test_mixed_dtype_window_payload_verifies_per_dtype_bucket():
    """bf16 params + the fp32 a/b/α (and the model's fp32 score_head bias)
    make the bucketed averaging emit one all-reduce result PER DTYPE — two
    buckets, as two ops or as one tuple op when XLA's combiner fuses them.
    ``verify_window_payload`` must accept that as the documented layout
    (one collective result per dtype bucket, total == payload, per-dtype
    bytes == ``window_payload_by_dtype``) instead of failing spuriously,
    while still rejecting a wrong op count and a wrong per-dtype split."""
    _run("""
    from repro.analysis import audit as A
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    K, I, B = 8, 2, 8
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7,
                           param_dtype=jnp.bfloat16)
    st0 = coda.init_state(jax.random.PRNGKey(0), mcfg, ccfg)
    dts = {l.dtype for l in jax.tree_util.tree_leaves(st0["params"])}
    assert dts == {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}, dts
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh,
                             donate=False)
    wb = {"features": jax.ShapeDtypeStruct((I, K, B, 16), jnp.float32),
          "labels": jax.ShapeDtypeStruct((I, K, B), jnp.float32)}
    sts = jax.eval_shape(lambda s: s, st0)
    txt = exe.window_fn(sts, wb).lower(
        sts, wb, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()

    payload = coda.window_payload_bytes(st0)
    by_dtype = coda.window_payload_by_dtype(st0)
    assert set(by_dtype) == {"bf16", "f32"}
    ops = A.assert_window_payload(txt, payload, by_dtype=by_dtype)
    # one all-reduce result per dtype bucket
    assert sum(len(o["components"]) for o in ops) == 2, ops
    try:
        A.assert_window_payload(txt, payload, count=len(ops) + 1)
        raise SystemExit("a wrong op count must fail on a mixed-dtype window")
    except AssertionError:
        pass
    try:
        A.assert_window_payload(txt, payload,
                                by_dtype={"bf16": payload, "f32": 0})
        raise SystemExit("wrong per-dtype split must fail")
    except AssertionError:
        pass
    # the bf16 sharded window still matches the vmap oracle
    key = jax.random.PRNGKey(1)
    ky, kx = jax.random.split(key)
    y = (jax.random.uniform(ky, (I, K, B)) < 0.7).astype(jnp.float32)
    x = jax.random.normal(kx, (I, K, B, 16))
    wbr = {"features": x, "labels": y}
    st1, _ = exe.window_step(exe.place(st0), wbr, 0.1)
    r1, _ = coda.window_step(mcfg, ccfg, st0, wbr, 0.1)
    assert_trees_close(
        {k: v.astype(jnp.float32) if hasattr(v, "astype") else v
         for k, v in st1.items() if k in ("a", "b", "alpha")},
        {k: v for k, v in r1.items() if k in ("a", "b", "alpha")},
        1e-5, "bf16/scalars")
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(st1["params"])[0],
            jax.tree_util.tree_flatten_with_path(r1["params"])[0]):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                    b.astype(jnp.float32))))
        assert err < 2e-2, (jax.tree_util.keystr(p), err)  # bf16 tolerance
    print("ALL OK")
    """)


def test_executor_instance_survives_changing_window_length():
    """Regression for the ``_fns`` cache: its key is (treedef, ndim) only,
    so two window lengths I₁ ≠ I₂ (same rank, different shape) hit the
    SAME cache entry and rely on jit retracing under it.  One executor
    instance driven at I=2 then I=5, with both ``communicate`` flags, must
    keep matching the oracle — a stale lowered shape would either crash or
    silently produce wrong results."""
    _run("""
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    K = 8
    ccfg, st0, _, _ = make_case(K, 2)
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh,
                             donate=False)
    st = exe.place(st0)
    rt = st0
    for I, communicate in [(2, True), (5, True), (2, False), (5, False),
                           (3, True)]:
        _, _, wb, _ = make_case(K, I, seed=I)
        st, losses = exe.window_step(st, wb, 0.1, communicate=communicate)
        rt, rl = coda.window_step(mcfg, ccfg, rt, wb, 0.1,
                                  communicate=communicate)
        assert losses.shape == (I, K), (I, losses.shape)
        assert_trees_close(st, rt, 1e-5, f"I={I} comm={communicate}")
        np.testing.assert_allclose(np.asarray(jnp.mean(losses, axis=1)),
                                   np.asarray(rl), atol=1e-5)
        print("OK", I, communicate)
    # the cache really is shared per (tag, treedef, ndim): 2 entries
    # (communicate True/False), not one per window length
    assert len(exe._fns) == 2, len(exe._fns)
    print("ALL OK")
    """)


# --------------------------------------------------------------------------
# int8 averaging properties (single-device oracle; no mesh needed)
# --------------------------------------------------------------------------
def _toy_state(key, K, shapes=((4, 3), (5,))):
    ks = jax.random.split(key, len(shapes) + 3)
    params = {f"w{i}": jax.random.normal(k, (K,) + s)
              for i, (k, s) in enumerate(zip(ks, shapes))}
    z = lambda k: jax.random.normal(k, (K,))
    return {"params": params,
            "duals": {"a": z(ks[-3]), "b": z(ks[-2]), "alpha": z(ks[-1])},
            "ref_params": params,
            "ref_duals": {"a": jnp.zeros((K,)), "b": jnp.zeros((K,))}}


@settings(max_examples=15, deadline=None)
@given(c=st.floats(-3.0, 3.0), spread=st.floats(0.0, 2.0),
       seed=st.integers(0, 1000))
def test_int8_average_exact_on_uniform_tensors(c, spread, seed):
    """When every worker's tensor is per-tensor uniform, quantization maps
    each value to exactly ±127 of its own scale — the int8 average equals
    the exact average to fp32 precision."""
    K = 4
    cs = c + spread * jnp.arange(K)  # per-worker constants
    state = _toy_state(jax.random.PRNGKey(seed), K)
    state["params"] = {
        "w0": jnp.broadcast_to(cs[:, None, None], (K, 4, 3)).copy()}
    state["duals"] = {"a": cs.astype(jnp.float32),
                      "b": -cs.astype(jnp.float32),
                      "alpha": cs.astype(jnp.float32)}
    got = coda.average(state, compress="int8")
    want = coda.average(state)
    for ka, kb in zip(jax.tree_util.tree_leaves(got),
                      jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(ka), np.asarray(kb), atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(scale=st.floats(0.01, 10.0), seed=st.integers(0, 1000))
def test_int8_average_error_bounded_by_quantization_step(scale, seed):
    """|int8-avg − exact-avg| ≤ one quantization step of the max-abs scale
    (elementwise error ≤ scale_k/2 per worker; averaging cannot grow it)."""
    K = 4
    state = _toy_state(jax.random.PRNGKey(seed), K)
    state["params"] = jax.tree_util.tree_map(lambda x: x * scale,
                                             state["params"])
    got = coda.average(state, compress="int8")
    want = coda.average(state)
    for leaf_q, leaf_x, leaf_o in zip(
            jax.tree_util.tree_leaves(got["params"]),
            jax.tree_util.tree_leaves(state["params"]),
            jax.tree_util.tree_leaves(want["params"])):
        step = float(jnp.max(jnp.abs(leaf_x)) / 127.0)
        err = float(jnp.max(jnp.abs(leaf_q - leaf_o)))
        assert err <= step + 1e-7, (err, step)


def test_int8_sharded_bucket_matches_oracle_without_mesh():
    """The bucketed averaging helper (what shard_map runs per shard) must
    equal coda.average(compress='int8') even in its degenerate no-mesh form
    (wa=(), K_loc=K)."""
    from repro.core import coda_sharded
    state = _toy_state(jax.random.PRNGKey(3), 4)
    got = coda_sharded._bucketed_average(state, (), "int8")
    want = coda.average(state, compress="int8")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# --------------------------------------------------------------------------
# driver / executor surface
# --------------------------------------------------------------------------
def test_fit_vmap_executor_donated_driver():
    """The jit-once donated-buffer driver must run multi-stage training
    without donation aliasing errors and keep the comm accounting."""
    from repro.data import DataConfig, ShardedDataset
    key = jax.random.PRNGKey(0)
    K = 4
    ds = ShardedDataset(key, DataConfig(kind="features", n_features=16),
                        1024, K, target_p=0.7)
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=ds.p_pos)
    sched = schedules.ScheduleConfig(n_workers=K, eta0=0.5, T0=8, I0=4)
    res = coda.fit(key, MCFG, ccfg, sched, 2,
                   sample_window=lambda k, i: ds.sample_window(k, i, 16),
                   sample_alpha_batch=lambda k, m: ds.sample_alpha_batch(k, m),
                   executor="vmap")
    sl = schedules.stages(sched, 2)
    assert res.comm_rounds == coda.comm_rounds(sl)
    assert res.iterations == sum(s.T for s in sl)
    assert all(np.isfinite(h[2]) for h in res.history)


def test_make_executor_rejects_bad_flags():
    ccfg = coda.CoDAConfig(n_workers=2)
    try:
        coda.make_executor(MCFG, ccfg, "shard_map")
        raise AssertionError("expected ValueError for missing mesh")
    except ValueError:
        pass
    try:
        coda.make_executor(MCFG, ccfg, "pmap")
        raise AssertionError("expected ValueError for unknown executor")
    except ValueError:
        pass
