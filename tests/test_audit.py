"""Red-team tests for the compiled-program auditor (analysis/audit.py).

Every rule must FAIL on a deliberately-violating program and pass on the
real stack — a rule that cannot reject its counterexample is decoration,
not a gate.  The violating programs are real jitted artifacts where jax
can produce them in-process (R2's unaliasable donation, R3's f64 /
callback / narrow-accumulation jaxprs, R5's broken geometry) and
hand-written HLO where the violation is about wire schedule shape (R1's
smuggled collective, degenerate ring).  The real-stack pass runs the full
capture + rule engine over the training executors and the serving engine
in an 8-device subprocess, including a shard_map local step with a
smuggled pmean that R1 must reject.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import audit as A

# hand-written window HLO: ONE f32 all-reduce of 400 bytes
_WINDOW_OK = "%ar = f32[100]{0} all-reduce(%p0), replica_groups={{0,1}}"
# ...and the violations
_WINDOW_SMUGGLED = _WINDOW_OK + "\n%ar2 = f32[25]{0} all-reduce(%p1)"
_WINDOW_WRONG_KIND = "%ag = f32[100]{0} all-gather(%p0)"


def _prog(name, hlo, expect):
    return A.CompiledProgram(name=name, hlo_text=hlo, expect=expect)


# --------------------------------------------------------------------------
# R1 — collective placement
# --------------------------------------------------------------------------
def test_r1_collective_free_rejects_smuggled_collective():
    prog = _prog("local_step", _WINDOW_OK, {"collectives": {"kind": "none"}})
    findings = A.rule_collective_placement(prog)
    assert findings and findings[0].rule == "R1"
    clean = _prog("local_step", "%d = f32[8,8]{1,0} dot(%a, %b)",
                  {"collectives": {"kind": "none"}})
    assert A.rule_collective_placement(clean) == []


def test_r1_window_rejects_second_all_reduce_and_wrong_kind():
    ok = _prog("window", _WINDOW_OK,
               {"collectives": {"kind": "window", "expected_bytes": 400}})
    assert A.rule_collective_placement(ok) == []
    for bad_hlo in (_WINDOW_SMUGGLED, _WINDOW_WRONG_KIND, ""):
        bad = _prog("window", bad_hlo,
                    {"collectives": {"kind": "window",
                                     "expected_bytes": 400}})
        assert A.rule_collective_placement(bad), bad_hlo
    short = _prog("window", _WINDOW_OK,
                  {"collectives": {"kind": "window", "expected_bytes": 800}})
    assert "mismatch" in A.rule_collective_placement(short)[0].message


def test_r1_ring_rejects_blocking_all_reduce_and_wrong_hops():
    hops = "\n".join(
        f"%cp{i} = f32[50]{{0}} collective-permute(%x{i})" for i in range(4))
    bad = _prog("pair", hops + "\n" + _WINDOW_OK,
                {"collectives": {"kind": "ring", "n_hops": 4}})
    msgs = [f.message for f in A.rule_collective_placement(bad)]
    assert any("blocking" in m for m in msgs)
    wrong_count = _prog("pair", hops,
                        {"collectives": {"kind": "ring", "n_hops": 6}})
    assert A.rule_collective_placement(wrong_count)


def test_r1_gather_pair_rejects_non_s8_payload():
    ok_hlo = ("%ag1 = s8[800]{0} all-gather(%p)\n"
              "%ag2 = f32[96]{0} all-gather(%s)")
    ok = _prog("int8", ok_hlo, {"collectives": {
        "kind": "gather_pair", "payload_bytes": 148, "n_workers": 8}})
    assert A.rule_collective_placement(ok) == []
    f32_leak = _prog("int8", "%ag = f32[296]{0} all-gather(%p)",
                     {"collectives": {"kind": "gather_pair",
                                      "payload_bytes": 148, "n_workers": 8}})
    assert A.rule_collective_placement(f32_leak)   # bytes match, dtype wrong
    reduce_not_gather = _prog(
        "int8", _WINDOW_OK, {"collectives": {
            "kind": "gather_pair", "payload_bytes": 50, "n_workers": 8}})
    assert A.rule_collective_placement(reduce_not_gather)


def test_window_payload_split_validation_still_raises_valueerror():
    """Parameter-misuse semantics survived the rule-engine refactor."""
    with pytest.raises(ValueError, match="go together"):
        A.assert_window_payload("", 100, baseline_bytes=90)
    _, problems = A.window_payload_problems(
        _WINDOW_OK, 400, baseline_bytes=320, delta_bytes=80)
    assert problems == []


# --------------------------------------------------------------------------
# R2 — donation audit (real compiled programs)
# --------------------------------------------------------------------------
def test_r2_rejects_dropped_donation():
    """Donating a buffer no output can reuse (shape mismatch) must be a
    finding; a same-shape update must alias and pass."""
    x = jnp.arange(4, dtype=jnp.float32)

    grow = jax.jit(lambda v: jnp.concatenate([v, v]), donate_argnums=0)
    bad = A.CompiledProgram.capture("grow", grow, x, donated_leaves=1)
    findings = A.rule_donation(bad)
    assert findings and "donated" in findings[0].message

    inc = jax.jit(lambda v: v + 1, donate_argnums=0)
    good = A.CompiledProgram.capture("inc", inc, x, donated_leaves=1)
    assert A.rule_donation(good) == []


def test_r2_deleted_unused_input_is_not_a_dropped_donation():
    """XLA deleting a donated-but-unused input leaves nothing to alias —
    that is dead-code elimination, not a lost donation."""
    f = jax.jit(lambda v, unused: v * 2, donate_argnums=(0, 1))
    x = jnp.arange(4, dtype=jnp.float32)
    prog = A.CompiledProgram.capture("dce", f, x, x + 1, donated_leaves=2)
    assert A.rule_donation(prog) == []


# --------------------------------------------------------------------------
# R3 — host-sync / dtype lint (real jaxprs)
# --------------------------------------------------------------------------
def _jaxpr_of(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def test_r3_rejects_f64_literal_in_hot_path():
    with jax.enable_x64(True):
        jaxpr = _jaxpr_of(lambda v: v * jnp.float64(2.5),
                          jnp.arange(4, dtype=jnp.float64))
    problems = A.jaxpr_problems(jaxpr)
    assert any("f64" in p for p in problems)
    assert A.jaxpr_problems(jaxpr, allow_f64=True) == []


def test_r3_rejects_host_callback():
    def step(v):
        jax.debug.print("v={v}", v=v[0])
        return v + 1
    problems = A.jaxpr_problems(_jaxpr_of(step, jnp.zeros(4)))
    assert any("callback" in p for p in problems)


def test_r3_recurses_into_scan_bodies():
    def windowed(v):
        def body(c, _):
            jax.debug.print("c={c}", c=c[0])
            return c + 1, None
        out, _ = jax.lax.scan(body, v, None, length=3)
        return out
    problems = A.jaxpr_problems(_jaxpr_of(windowed, jnp.zeros(4)))
    assert any("callback" in p for p in problems)


def test_r3_rejects_sub_fp32_accumulation():
    x = jnp.zeros((8, 8), jnp.bfloat16)
    narrow_dot = _jaxpr_of(
        lambda a: jax.lax.dot_general(a, a, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.bfloat16), x)
    assert any("accumulate" in p for p in A.jaxpr_problems(narrow_dot))

    # jnp.sum upcasts even under dtype=bfloat16, so a narrow reduction can
    # only enter a jaxpr through the raw primitive — bind it directly
    narrow_sum = _jaxpr_of(
        lambda a: jax.lax.reduce_sum_p.bind(a, axes=(0, 1)), x)
    assert any("accumulate" in p for p in A.jaxpr_problems(narrow_sum))

    # jnp.sum's default upcast and an fp32-accumulating dot are both clean
    wide = _jaxpr_of(
        lambda a: jnp.sum(a) + jax.lax.dot_general(
            a, a, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).sum(), x)
    assert A.jaxpr_problems(wide) == []


# --------------------------------------------------------------------------
# R4 — recompile budget
# --------------------------------------------------------------------------
def test_r4_rejects_budget_overrun():
    over = A.CompiledProgram(name="serve", compile_count=3,
                             expect={"compiles": {"exact": 2}})
    assert A.rule_recompile_budget(over)
    under = A.CompiledProgram(name="serve", compile_count=1,
                              expect={"compiles": {"exact": 2}})
    assert A.rule_recompile_budget(under)   # exact means exact: 1 != 2
    at_max = A.CompiledProgram(name="fit", compile_count=2,
                               expect={"compiles": {"max": 2}})
    assert A.rule_recompile_budget(at_max) == []
    past_max = A.CompiledProgram(name="fit", compile_count=3,
                                 expect={"compiles": {"max": 2}})
    assert A.rule_recompile_budget(past_max)


# --------------------------------------------------------------------------
# R5 — Pallas static checks
# --------------------------------------------------------------------------
def test_r5_rejects_broken_geometry_and_off_tpu_interpret():
    bad_div = A.PallasLaunch(kernel="k", grid=(3,),
                             blocks={"t": (100, 32)})      # 100 % 32 != 0
    assert A.rule_pallas_static(bad_div)
    bad_grid = A.PallasLaunch(kernel="k", grid=(0, 4),
                              blocks={"t": (64, 32)})
    assert A.rule_pallas_static(bad_grid)
    bad_align = A.PallasLaunch(kernel="k", grid=(1,), blocks={},
                               alignments={"bn%128": (96, 128)})
    assert A.rule_pallas_static(bad_align)
    smuggled_interpret = A.PallasLaunch(kernel="k", grid=(1,),
                                        blocks={"t": (32, 32)},
                                        interpret=True, impl="auto")
    msgs = [f.message for f in A.rule_pallas_static(smuggled_interpret)]
    assert any("interpret" in m for m in msgs)
    explicit = A.PallasLaunch(kernel="k", grid=(1,), blocks={"t": (32, 32)},
                              interpret=True, impl="pallas")
    assert A.rule_pallas_static(explicit) == []


def test_r5_real_kernel_geometry_passes_including_ragged_tails():
    for impl in ("auto", "ref", "pallas"):
        for launch in A.capture_kernel_launches(impl=impl):
            assert A.launch_problems(launch) == [], launch
    # ragged problem sizes that historically tripped tile math
    ragged = A.capture_kernel_launches(
        impl="ref", shapes={"moe": (7, 5, 3, 9), "auc": (12,),
                            "prox": (5,), "flash": (1, 8, 4, 2, 8, 64)})
    for launch in ragged:
        assert A.launch_problems(launch) == [], launch
    assert A.dispatch_problems() == []


# --------------------------------------------------------------------------
# report plumbing
# --------------------------------------------------------------------------
def test_report_aggregates_and_serializes():
    bad = _prog("w", _WINDOW_SMUGGLED,
                {"collectives": {"kind": "window", "expected_bytes": 400}})
    report = A.run_rules([bad], A.capture_kernel_launches(impl="ref"),
                         check_dispatch=False)
    assert not report.ok
    with pytest.raises(AssertionError, match="audit failed"):
        report.raise_if_failed()
    d = report.to_dict()
    assert d["n_findings"] >= 1 and d["rules"]["R1"]["findings"]
    ok = A.run_rules([_prog("w", _WINDOW_OK, {"collectives": {
        "kind": "window", "expected_bytes": 400}})])
    assert ok.ok and ok.to_dict()["ok"]
    ok.raise_if_failed()                     # no-op on a clean report


# --------------------------------------------------------------------------
# the real stack, on a real 8-device mesh (subprocess: XLA_FLAGS must be
# set before jax initialises its backend)
# --------------------------------------------------------------------------
def _run(script: str, timeout=900):
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.analysis import audit as A
        from repro.configs.base import mlp_config
        from repro.core import coda
        mcfg = mlp_config(n_features=16, d=32)
    """)
    r = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ALL OK" in r.stdout, r.stdout[-2000:]


def test_real_stack_passes_and_smuggled_pmean_fails():
    """The full capture + rule engine over both executors passes on the
    real stack, and a shard_map local-step body with a smuggled pmean is
    rejected by R1 — the audit can tell the real program from a subtly
    broken one on the same mesh."""
    _run("""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = jax.make_mesh((8, 1), ("data", "model"))
    for algorithm in ("coda", "codasca"):
        ccfg = coda.CoDAConfig(n_workers=8, algorithm=algorithm)
        programs = A.capture_training_programs(
            mcfg, ccfg, executor="shard_map", mesh=mesh,
            window_lens=(1, 2), tag=f"sharded/{algorithm}")
        programs += A.capture_training_programs(
            mcfg, ccfg, executor="vmap", window_lens=(1, 2),
            tag=f"vmap/{algorithm}")
        A.run_rules(programs, check_dispatch=False).raise_if_failed()

    # red-team: a "local step" that sneaks a pmean over the worker axis
    def leaky_local_step(v):
        return v - 0.1 * jax.lax.pmean(v * v, "data")

    leaky = jax.jit(shard_map(
        leaky_local_step, mesh=mesh, in_specs=P("data"),
        out_specs=P("data")))
    prog = A.CompiledProgram.capture(
        "leaky_local_step", leaky, jnp.zeros((8, 4)),
        expect={"collectives": {"kind": "none"}})
    report = A.run_rules([prog], check_dispatch=False)
    assert not report.ok, "R1 must reject the smuggled pmean"
    assert any(f.rule == "R1" for f in report.findings)
    print("ALL OK")
    """)


def test_real_serving_stack_passes_audit():
    """The serving engine's two chunk programs pass every rule, and the R4
    compile budget of exactly two executables holds over a live mixed
    prefill/decode workload."""
    _run("""
    programs = A.capture_serving_programs(slots=2, max_len=32,
                                          prefill_chunk=4)
    report = A.run_rules(programs, check_dispatch=False)
    report.raise_if_failed()
    cache = [p for p in programs if p.name.endswith("chunk_step_cache")]
    assert cache and cache[0].compile_count == 2, cache
    print("ALL OK")
    """)
