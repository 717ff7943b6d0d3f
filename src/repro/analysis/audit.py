"""Compiled-program auditor: a rule engine over jaxprs + optimized HLO.

PRs 1–7 accumulated one-off compiled-artifact asserts — window-payload
checks, ring-schedule checks, payload-split checks — each re-parsing HLO
text its own way.  This module promotes them into a single static-analysis
layer: every jitted program in the repo is captured as a
:class:`CompiledProgram` record (closed jaxpr, optimized HLO text,
``cost_analysis``, input/output aliasing, compile count) and run through a
fixed rule set:

  * **R1 collective-placement** — the paper's headline claim as a static
    property: local-step bodies are collective-free; a window is exactly
    ONE bucketed all-reduce of the documented payload (or the asserted
    chunked ppermute ring schedule under ``overlap_chunks``; or the
    s8 + f32-scale all-gather pair under ``avg_compress="int8"``).  The
    historical ``analysis/hlo.verify_window_payload`` /
    ``verify_overlapped_window`` entry points are thin wrappers over the
    R1 checkers here (:func:`assert_window_payload`,
    :func:`assert_overlapped_window`).
  * **R2 donation-audit** — every buffer donated at the jit boundary is
    actually aliased in the compiled output (``input_output_alias``); a
    dropped donation silently doubles peak memory and is a hard failure.
  * **R3 host-sync/dtype lint** — a recursive jaxpr walk: no f64 creep, no
    host callbacks or device transfers inside jitted hot paths, and
    matmuls/reductions over sub-fp32 operands must accumulate in ≥ fp32.
  * **R4 recompile-budget** — callables carry a compile count (jit cache
    size) pinned against the documented budget: the serve engine compiles
    exactly two programs (C ∈ {prefill_chunk, 1}), the training executors
    compile once per distinct window length and never re-trace.
  * **R5 Pallas static checks** — tile-shape divisibility, grid bounds and
    alignment for the kernels' launch geometry (each kernel module exposes
    the ``launch_geometry`` it launches with), plus the dispatch
    invariant that interpret mode is never selectable off-TPU except via
    the explicit ``impl="pallas"`` override.

The second half of the module is the program *registry*: capture helpers
that build the records for each distinct program in the repo —
``core/coda.py``'s vmap oracle, ``core/coda_sharded.py``'s shard_map
window / fused pair / stage programs, ``serving/engine.py``'s two chunk
programs, and the ``kernels/`` launch seam.  ``scripts/audit.py`` drives
them over the full executor × algorithm × dtype × schedule matrix and
emits a JSON artifact; CI gates on it.  Rule semantics are documented in
docs/analysis.md; red-team counterexamples live in tests/test_audit.py.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis import hlo as H

# --------------------------------------------------------------------------
# records
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CompiledProgram:
    """One distinct jitted program, as captured from its compiled artifact.

    ``expect`` carries the per-program rule parameters:
      * ``"collectives"`` (R1) — ``{"kind": "none"}`` |
        ``{"kind": "window", ...verify params}`` |
        ``{"kind": "ring", "n_hops": H, "n_chains": C|None}`` |
        ``{"kind": "gather_pair", "payload_bytes": B, "n_workers": K}``
      * ``"compiles"`` (R4) — ``{"exact": N}`` or ``{"max": N}``
    Rules without an expectation entry fall back to their defaults (R2/R3
    always run; R1/R4 are skipped when unparameterized).
    """
    name: str
    hlo_text: str = ""
    jaxpr: Any = None                    # ClosedJaxpr | None
    cost: dict = dataclasses.field(default_factory=dict)
    donated_args: int = 0                # donated leaves at the jit boundary
    nondonated_args: int = 0             # non-donated input leaves
    aliased_args: int | None = None   # parsed from HLO when None
    compile_count: int | None = None  # jit cache size behind the callable
    expect: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def capture(cls, name: str, fn, *args, expect: dict | None = None,
                donated_leaves: int = 0, compile_count: int | None = None,
                **kwargs) -> "CompiledProgram":
        """Lower + compile a jitted callable on abstract (or concrete) args
        and record jaxpr, optimized HLO, and cost analysis."""
        compiled = fn.lower(*args, **kwargs).compile()
        txt = compiled.as_text()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        try:
            jaxpr = fn.trace(*args, **kwargs).jaxpr
        except (AttributeError, TypeError):  # pre-AOT-API jax fallback
            jaxpr = None
        n_inputs = len(jax.tree_util.tree_leaves((args, kwargs)))
        return cls(name=name, hlo_text=txt, jaxpr=jaxpr,
                   cost=dict(cost or {}), donated_args=donated_leaves,
                   nondonated_args=max(0, n_inputs - donated_leaves),
                   aliased_args=alias_count(txt), compile_count=compile_count,
                   expect=dict(expect or {}))


@dataclasses.dataclass
class PallasLaunch:
    """Static launch geometry of one Pallas kernel call (R5).

    ``blocks`` maps a named grid axis to ``(padded_extent, block)`` — the
    divisibility obligation; ``alignments`` maps a label to
    ``(value, multiple)`` — TPU tiling obligations the kernel's own math is
    supposed to guarantee.  ``interpret``/``impl`` record what the dispatch
    seam actually selected."""
    kernel: str
    grid: tuple
    blocks: dict
    alignments: dict = dataclasses.field(default_factory=dict)
    interpret: bool = False
    impl: str = "auto"


@dataclasses.dataclass
class Finding:
    rule: str
    program: str
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.program}: {self.message}"


@dataclasses.dataclass
class AuditReport:
    findings: list
    checked: list                        # (rule, program-name) pairs

    @property
    def ok(self) -> bool:
        return not self.findings

    def raise_if_failed(self) -> None:
        if self.findings:
            raise AssertionError(
                "audit failed:\n" + "\n".join(str(f) for f in self.findings))

    def to_dict(self) -> dict:
        per_rule: dict = {}
        for rule, prog in self.checked:
            per_rule.setdefault(rule, {"checked": [], "findings": []})
            per_rule[rule]["checked"].append(prog)
        for f in self.findings:
            per_rule.setdefault(f.rule, {"checked": [], "findings": []})
            per_rule[f.rule]["findings"].append(
                {"program": f.program, "message": f.message})
        return {"ok": self.ok, "n_checked": len(self.checked),
                "n_findings": len(self.findings), "rules": per_rule}

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)


# --------------------------------------------------------------------------
# R1 — collective placement (the refactored window/ring checkers)
# --------------------------------------------------------------------------
def window_payload_problems(hlo_text: str, expected_bytes: int, *,
                            op: str = "all-reduce",
                            count: int | None = None,
                            by_dtype: dict | None = None,
                            baseline_bytes: int | None = None,
                            delta_bytes: int | None = None,
                            opt_bytes: int | None = None):
    """The window-payload check as a pure function: returns
    ``(collective op records, problems)`` instead of raising, so it can be
    an R1 rule instance AND back the assert-style entry points.  Parameter
    semantics are documented on ``analysis/hlo.verify_window_payload``
    (which delegates here).  Misuse of the parameters themselves still
    raises ValueError.

    ``opt_bytes``: per-worker size of the local optimizer state
    (``coda.opt_state_bytes``).  Preconditioning is strictly local — the
    window collective must NEVER carry it — so when the shipped bytes
    exceed the expectation by exactly this amount the mismatch message
    names the cause instead of leaving a raw byte delta to decode."""
    if (baseline_bytes is None) != (delta_bytes is None):
        raise ValueError("baseline_bytes and delta_bytes go together")
    problems = []
    if baseline_bytes is not None and \
            baseline_bytes + delta_bytes != expected_bytes:
        problems.append(
            f"payload delta mismatch: baseline {baseline_bytes} + delta "
            f"{delta_bytes} != expected {expected_bytes}")
    ops = H.collective_ops(hlo_text)
    stray = [o for o in ops if o["op"] != op]
    if stray:
        problems.append(
            f"expected only {op} ops, found "
            f"{[(o['op'], o['bytes']) for o in stray]}")
    if count is not None:
        if len(ops) != count:
            problems.append(
                f"expected exactly {count} {op} op(s), found "
                f"{[(o['op'], o['bytes']) for o in ops]}")
    elif by_dtype is None:
        seen: dict = {}
        for o in ops:
            for dt in o["by_dtype"]:
                seen[dt] = seen.get(dt, 0) + 1
        dup = {dt: n for dt, n in seen.items() if n > 1}
        if dup or not ops:
            problems.append(
                f"expected one {op} per payload dtype bucket, found "
                f"{[(o['op'], o['by_dtype']) for o in ops]}")
    if by_dtype is not None:
        if sum(by_dtype.values()) != expected_bytes:
            problems.append(
                f"by_dtype buckets sum to {sum(by_dtype.values())}, "
                f"expected_bytes says {expected_bytes}")
        # one bucket per op result: a combined tuple op carries several
        unmatched = [c for o in ops for c in o["components"]]
        for tag, b in sorted(by_dtype.items()):
            hit = None
            for i, c in enumerate(unmatched):
                if c == {tag: b}:
                    hit = i          # verbatim wire dtype
                    break
                if tag in ("bf16", "f16") and c == {"f32": 2 * b}:
                    hit = i          # float-normalized to f32, same elements
                    break
            if hit is None:
                problems.append(
                    f"no {op} carries the {tag} bucket of {b} bytes "
                    f"(ops: {[(o['op'], o['components']) for o in ops]})")
                continue
            unmatched.pop(hit)
        if unmatched:
            msg = (f"stray {op} results beyond the accounted dtype buckets: "
                   f"{unmatched}")
            stray_b = sum(sum(c.values()) for c in unmatched)
            if opt_bytes and stray_b == opt_bytes:
                msg += (f" — the stray bytes equal the per-worker optimizer "
                        f"state ({opt_bytes} B): optimizer state leaked "
                        f"onto the wire")
            problems.append(msg)
    else:
        total = sum(o["bytes"] for o in ops)
        if total != expected_bytes:
            msg = (f"window payload mismatch: HLO ships {total} bytes, "
                   f"accounting says {expected_bytes} "
                   f"({[(o['op'], o['bytes']) for o in ops]})")
            if opt_bytes and total == expected_bytes + opt_bytes:
                msg += (f" — the excess equals the per-worker optimizer "
                        f"state ({opt_bytes} B): optimizer state leaked "
                        f"onto the wire")
            problems.append(msg)
    return ops, problems


def overlapped_window_problems(hlo_text: str, *, n_hops: int,
                               n_chains: int | None = None,
                               require_compute_between: bool = True):
    """The overlapped-ring schedule check as a pure function: returns
    ``(permute op records, problems)``.  Semantics documented on
    ``analysis/hlo.verify_overlapped_window`` (which delegates here)."""
    problems = []
    ops = H.collective_ops(hlo_text)
    stray = [o for o in ops if o["op"] != "collective-permute"]
    if stray:
        problems.append(
            "overlapped window must not contain blocking collectives, found "
            f"{[(o['op'], o['bytes']) for o in stray]}")
    if len(ops) != n_hops:
        problems.append(
            f"expected {n_hops} collective-permute hops, found {len(ops)}")
    if n_chains is not None:
        got = H.permute_chain_components(hlo_text)
        if got != n_chains:
            problems.append(
                f"expected {n_chains} independent permute chains, found "
                f"{got} — the chunked ring degenerated (de-chunked or "
                "cross-chunk serialized)")
    if require_compute_between and ops and not stray:
        dotted = H._dot_bearing_computations(hlo_text)
        lines = hlo_text.splitlines()
        hop_idx = [i for i, ln in enumerate(lines) if H._OP_RE.search(ln)]
        found = False
        for ln in lines[hop_idx[0] + 1:hop_idx[-1]]:
            if H._DOT_RE.search(ln):          # an unfused dot right there
                found = True
                break
            if any(c.lstrip("%") in dotted
                   for c in H._CALLEE_RE.findall(ln)):
                found = True
                break
        if not found:
            problems.append(
                "no dot-bearing compute scheduled between the first and last "
                "ring hop — the two windows were not fused around the "
                "averaging")
    return ops, problems


def gather_pair_problems(hlo_text: str, *, payload_bytes: int,
                         n_workers: int):
    """The int8 compressed-averaging wire check: every collective is an
    all-gather, the wire carries only the s8 payload plus fp32 scales, and
    the gathered bytes per worker equal the documented compressed payload
    (``coda.window_payload_bytes(state, "int8")``)."""
    problems = []
    ops = H.collective_ops(hlo_text)
    stray = [o for o in ops if o["op"] != "all-gather"]
    if stray:
        problems.append(
            "int8 averaging must ship all-gather only, found "
            f"{[(o['op'], o['bytes']) for o in stray]}")
    by_dtype: dict = {}
    for o in ops:
        for dt, b in o["by_dtype"].items():
            by_dtype[dt] = by_dtype.get(dt, 0) + b
    extra = set(by_dtype) - {"s8", "f32"}
    if extra:
        problems.append(
            f"int8 wire must be s8 payload + f32 scales, found dtypes "
            f"{sorted(by_dtype)}")
    if ops and not by_dtype.get("s8"):
        problems.append(
            "int8 wire ships no s8 bytes — the payload left the worker "
            f"uncompressed (dtypes: {sorted(by_dtype)})")
    total = sum(by_dtype.values())
    if total != n_workers * payload_bytes:
        problems.append(
            f"gathered bytes {total} != K({n_workers}) × compressed payload "
            f"({payload_bytes})")
    return ops, problems


def assert_window_payload(hlo_text: str, expected_bytes: int, **kw):
    """Raise AssertionError on the first window-payload problem; return the
    collective op records on success.  The rule-engine entry point behind
    ``analysis/hlo.verify_window_payload`` — same raise/return contract."""
    ops, problems = window_payload_problems(hlo_text, expected_bytes, **kw)
    if problems:
        raise AssertionError(problems[0])
    return ops


def assert_overlapped_window(hlo_text: str, *, n_hops: int,
                             n_chains: int | None = None,
                             require_compute_between: bool = True):
    """Raise AssertionError on the first ring-schedule problem; return the
    permute op records on success (behind
    ``analysis/hlo.verify_overlapped_window``)."""
    ops, problems = overlapped_window_problems(
        hlo_text, n_hops=n_hops, n_chains=n_chains,
        require_compute_between=require_compute_between)
    if problems:
        raise AssertionError(problems[0])
    return ops


def rule_collective_placement(prog: CompiledProgram):
    """R1: collectives appear exactly where the algorithm says they do."""
    spec = prog.expect.get("collectives")
    if spec is None:
        return []
    kind = spec.get("kind")
    if kind == "none":
        ops = H.collective_ops(prog.hlo_text)
        if ops:
            return [Finding("R1", prog.name,
                            "must be collective-free, found "
                            f"{[(o['op'], o['bytes']) for o in ops]}")]
        return []
    if kind == "window":
        keys = ("op", "count", "by_dtype", "baseline_bytes", "delta_bytes",
                "opt_bytes")
        _, problems = window_payload_problems(
            prog.hlo_text, spec["expected_bytes"],
            **{k: spec[k] for k in keys if k in spec})
    elif kind == "ring":
        _, problems = overlapped_window_problems(
            prog.hlo_text, n_hops=spec["n_hops"],
            n_chains=spec.get("n_chains"),
            require_compute_between=spec.get("require_compute_between", True))
    elif kind == "gather_pair":
        _, problems = gather_pair_problems(
            prog.hlo_text, payload_bytes=spec["payload_bytes"],
            n_workers=spec["n_workers"])
    else:
        raise ValueError(f"unknown R1 expectation kind {kind!r}")
    return [Finding("R1", prog.name, p) for p in problems]


# --------------------------------------------------------------------------
# R2 — donation audit
# --------------------------------------------------------------------------
# one entry per aliased output buffer: "{1}: (0, {3}, may-alias)"; a single
# non-tuple output indexes as the empty shape path "{}: (0, {}, may-alias)"
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\(")


def alias_count(hlo_text: str) -> int:
    """Number of input/output alias entries in the optimized module header —
    one per parameter buffer XLA accepted for reuse.  A donated-but-dropped
    buffer has no entry."""
    for line in hlo_text.splitlines():
        if "HloModule" not in line:
            continue
        m = re.search(r"input_output_alias=\{(.*)$", line)
        if not m:
            return 0
        return len(_ALIAS_ENTRY_RE.findall(m.group(1)))
    return 0


_PARAM_RE = re.compile(r"=\s*[^=]*\bparameter\(\d+\)")


def entry_param_count(hlo_text: str) -> int:
    """Number of parameters the optimized ENTRY computation still has.
    XLA deletes unused inputs outright (e.g. a stage program's ``ref_*``
    anchors, whose outputs dedup onto the freshly averaged params), so
    ``donated − (inputs − entry params)`` is the number of donations that
    can possibly alias."""
    lines = hlo_text.splitlines()
    start = next((i for i, ln in enumerate(lines)
                  if ln.startswith("ENTRY ")), None)
    if start is None:
        return 0
    n = 0
    for ln in lines[start + 1:]:
        if ln.strip() == "}":
            break
        if _PARAM_RE.search(ln):
            n += 1
    return n


def rule_donation(prog: CompiledProgram):
    """R2: every donated buffer that SURVIVES as an entry parameter is
    actually aliased in the compiled output.  (A donated input XLA deleted
    as unused never materializes, so there is nothing to alias — but a
    live parameter that was donated and not aliased means XLA rejected the
    reuse, silently doubling peak memory for that buffer: hard failure.)"""
    if prog.donated_args == 0:
        return []
    aliased = prog.aliased_args
    if aliased is None:
        aliased = alias_count(prog.hlo_text)
    n_params = entry_param_count(prog.hlo_text)
    # surviving donated params, assuming dropped inputs are donated ones
    # first (conservative: a dropped NON-donated input only lowers the bound)
    expected = max(0, n_params - prog.nondonated_args)
    if aliased < expected:
        return [Finding(
            "R2", prog.name,
            f"{prog.donated_args} buffers donated, {expected} survive as "
            f"entry parameters, but only {aliased} aliased in the compiled "
            "output — a dropped donation doubles peak memory for that "
            "buffer")]
    return []


# --------------------------------------------------------------------------
# R3 — host-sync / dtype lint on jaxprs
# --------------------------------------------------------------------------
# primitives that round-trip through the host (sync points) or move buffers
# between devices mid-program — none belong in a jitted hot path
_HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed", "host_callback_call",
})
_TRANSFER_PRIMS = frozenset({"device_put", "copy_to_host_async"})
# accumulation-bearing primitives: sub-fp32 operands must accumulate wider
_ACCUM_PRIMS = frozenset({"dot_general", "reduce_sum", "reduce_prod"})


def iter_eqns(jaxpr):
    """Yield every equation in a (Closed)Jaxpr, recursing through nested
    jaxprs in eqn params (scan/while/cond bodies, pjit/shard_map callees) —
    the hot-path ops hide there, not at the top level."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_eqns(sub)


def _is_float(dt) -> bool:
    return jnp.issubdtype(dt, jnp.floating)


def jaxpr_problems(jaxpr, *, allow_f64: bool = False) -> list:
    """The R3 lint over one program's jaxpr: f64 creep, host
    callbacks/transfers, sub-fp32 accumulation."""
    problems = []
    f64_hits = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _HOST_CALLBACK_PRIMS:
            problems.append(
                f"host callback `{name}` inside a jitted hot path (implicit "
                "host sync every step)")
        elif name in _TRANSFER_PRIMS:
            problems.append(
                f"device transfer `{name}` inside a jitted hot path")
        if not allow_f64:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and jnp.dtype(dt) == jnp.float64:
                    f64_hits.add(name)
        if name in _ACCUM_PRIMS:
            in_dts = [jnp.dtype(v.aval.dtype) for v in eqn.invars
                      if hasattr(getattr(v, "aval", None), "dtype")]
            narrow = [dt for dt in in_dts
                      if _is_float(dt) and dt.itemsize < 4]
            if not narrow:
                continue
            acc = eqn.params.get("preferred_element_type")
            if acc is None and eqn.outvars:
                acc = eqn.outvars[0].aval.dtype
            if acc is not None and _is_float(jnp.dtype(acc)) \
                    and jnp.dtype(acc).itemsize < 4:
                problems.append(
                    f"`{name}` over {narrow[0].name} operands accumulates in "
                    f"{jnp.dtype(acc).name} — reductions must accumulate in "
                    "≥ fp32")
    for name in sorted(f64_hits):
        problems.append(
            f"f64 value flows through `{name}` — f64 creep in a hot path "
            "(x64 mode doubles every downstream buffer)")
    return problems


def rule_host_sync(prog: CompiledProgram):
    """R3: jaxpr lint (skipped when the program carries no jaxpr)."""
    if prog.jaxpr is None:
        return []
    allow = prog.expect.get("allow_f64", False)
    return [Finding("R3", prog.name, p)
            for p in jaxpr_problems(prog.jaxpr, allow_f64=allow)]


# --------------------------------------------------------------------------
# R4 — recompile budget
# --------------------------------------------------------------------------
def rule_recompile_budget(prog: CompiledProgram):
    """R4: the callable behind this program compiled exactly/at-most the
    documented number of executables."""
    spec = prog.expect.get("compiles")
    if spec is None or prog.compile_count is None:
        return []
    if "exact" in spec and prog.compile_count != spec["exact"]:
        return [Finding(
            "R4", prog.name,
            f"compiled {prog.compile_count} programs, budget says exactly "
            f"{spec['exact']} — a shape/dtype leak is re-tracing the hot "
            "path")]
    if "max" in spec and prog.compile_count > spec["max"]:
        return [Finding(
            "R4", prog.name,
            f"compiled {prog.compile_count} programs, budget allows at most "
            f"{spec['max']}")]
    return []


# --------------------------------------------------------------------------
# R5 — Pallas static checks
# --------------------------------------------------------------------------
def launch_problems(launch: PallasLaunch) -> list:
    problems = []
    if not launch.grid or any(g < 1 for g in launch.grid):
        problems.append(f"degenerate grid {launch.grid}")
    for axis, (extent, block) in launch.blocks.items():
        if block < 1:
            problems.append(f"axis {axis}: non-positive block {block}")
            continue
        if extent % block != 0:
            problems.append(
                f"axis {axis}: padded extent {extent} not divisible by "
                f"block {block} — partial tiles would read out of bounds")
        if block > extent:
            problems.append(
                f"axis {axis}: block {block} exceeds padded extent {extent}")
    for label, (value, multiple) in launch.alignments.items():
        if value % multiple != 0:
            problems.append(
                f"alignment {label}: {value} is not a multiple of {multiple}")
    if launch.interpret and launch.impl != "pallas":
        problems.append(
            f"interpret-mode selected by impl={launch.impl!r} — only the "
            "explicit \"pallas\" override may interpret off-TPU")
    return problems


def rule_pallas_static(launch: PallasLaunch):
    return [Finding("R5", launch.kernel, p) for p in launch_problems(launch)]


def dispatch_problems() -> list:
    """The dispatch-seam half of R5 on the CURRENT backend: "auto" and
    "ref" must never select interpret mode; "pallas" interprets exactly
    when off-TPU."""
    from repro.kernels import ops as kops
    problems = []
    on_tpu = jax.default_backend() == "tpu"
    for impl in ("auto", "ref"):
        _, interpret = kops.dispatch(impl)
        if interpret:
            problems.append(
                f'dispatch("{impl}") selected interpret mode on the '
                f"{jax.default_backend()} backend")
    if kops.dispatch("pallas")[1] != (not on_tpu):
        problems.append(
            'dispatch("pallas") interpret flag disagrees with the backend')
    return problems


# --------------------------------------------------------------------------
# rule engine
# --------------------------------------------------------------------------
PROGRAM_RULES: dict = {
    "R1": rule_collective_placement,
    "R2": rule_donation,
    "R3": rule_host_sync,
    "R4": rule_recompile_budget,
}


def run_rules(programs, launches=(), *, rules=None,
              check_dispatch: bool = True) -> AuditReport:
    """Run the rule set over captured programs + kernel launches and return
    an :class:`AuditReport`.  ``rules`` narrows to a subset of
    {"R1".."R5"} (default: all)."""
    selected = set(rules) if rules is not None else {"R1", "R2", "R3", "R4",
                                                     "R5"}
    findings, checked = [], []
    for prog in programs:
        for rid, rule in PROGRAM_RULES.items():
            if rid not in selected:
                continue
            findings.extend(rule(prog))
            checked.append((rid, prog.name))
    if "R5" in selected:
        for launch in launches:
            findings.extend(rule_pallas_static(launch))
            checked.append(("R5", launch.kernel))
        if check_dispatch:
            findings.extend(Finding("R5", "kernels.ops.dispatch", p)
                            for p in dispatch_problems())
            checked.append(("R5", "kernels.ops.dispatch"))
    return AuditReport(findings=findings, checked=checked)


# --------------------------------------------------------------------------
# program registry: training executors
# --------------------------------------------------------------------------
def _payload_by_dtype_or_none(state, mult_aware=True, *, masked=False):
    from repro.core import coda
    by_dtype = coda.window_payload_by_dtype(state, masked=masked)
    return by_dtype if len(by_dtype) > 1 else None


def _fault_vectors(ccfg, K: int, *, abstract: bool):
    """The traced fault-vector argument the executors take when
    ``ccfg.faults_enabled`` (full participation — the R1/R4 contracts are
    shape properties, the schedule is data)."""
    if not ccfg.faults_enabled:
        return None
    if abstract:
        v = jax.ShapeDtypeStruct((K,), jnp.float32)
        return {"weights": v, "resync": v}
    return {"weights": jnp.ones((K,), jnp.float32),
            "resync": jnp.ones((K,), jnp.float32)}


def _abstract(tree):
    return jax.eval_shape(lambda t: t, tree)


def _mlp_window(mcfg, K: int, I: int, B: int):
    """Abstract window/alpha batches for the feature-vector configs the
    audit matrix trains (mirrors the tier-1 test batches)."""
    nf = mcfg.n_features
    wb = {"features": jax.ShapeDtypeStruct((I, K, B, nf), jnp.float32),
          "labels": jax.ShapeDtypeStruct((I, K, B), jnp.float32)}
    ab = {"features": jax.ShapeDtypeStruct((K, B, nf), jnp.float32),
          "labels": jax.ShapeDtypeStruct((K, B), jnp.float32)}
    return wb, ab


def _concrete_window(key, mcfg, K: int, I: int, B: int):
    nf = mcfg.n_features
    ky, kx = jax.random.split(key)
    y = (jax.random.uniform(ky, (I, K, B)) < 0.7).astype(jnp.float32)
    x = jax.random.normal(kx, (I, K, B, nf)) + 0.3 * (y[..., None] * 2 - 1)
    return {"features": x, "labels": y}


def capture_vmap_programs(mcfg, ccfg, *, I: int = 2, B: int = 8,
                          window_lens=(1, 2), seed: int = 0, tag: str = "vmap"):
    """Registry capture for the ``core/coda.py`` oracle executor.

    Lowers the window + stage programs (R1 "collective-free": the oracle's
    worker axis is a vmap axis, nothing may cross a wire), audits donation
    and the jaxpr, and drives the executor over ``window_lens`` to pin the
    R4 budget: one compile per distinct window length, none for repeats.
    """
    from repro.core import coda
    exe = coda.make_executor(mcfg, ccfg, "vmap", donate=True)
    K = ccfg.n_workers
    key = jax.random.PRNGKey(seed)
    st0 = coda.init_state(key, mcfg, ccfg)
    n_state_leaves = len(jax.tree_util.tree_leaves(st0))
    sts = _abstract(st0)
    wb, ab = _mlp_window(mcfg, K, I, B)
    eta = jax.ShapeDtypeStruct((), jnp.float32)
    fls = _fault_vectors(ccfg, K, abstract=True)
    fli = _fault_vectors(ccfg, K, abstract=False)
    wargs = (sts, wb, eta) if fls is None else (sts, wb, eta, fls)

    # R4: drive the executor eagerly — repeats must not re-trace, distinct
    # window lengths compile once each.  Under fault injection the fault
    # vectors are a fixed-shape traced arg, so the budget is unchanged.
    st = exe.place(st0)
    for wl in tuple(window_lens) + (window_lens[0],):
        wbi = _concrete_window(key, mcfg, K, wl, B)
        st, _ = exe.window_step(st, wbi, 0.1, **(
            {} if fli is None else {"faults": fli}))
    abi = jax.tree_util.tree_map(
        lambda l: l[0], _concrete_window(key, mcfg, K, 1, B))
    st = exe.stage_end(st, abi)

    # lower()/compile() below go through the AOT path and do not add cache
    # entries, so the budget is purely what the drive dispatched: one
    # executable per distinct window length, one stage program
    programs = [
        CompiledProgram.capture(
            f"{tag}/window", exe._wstep, *wargs,
            expect={"collectives": {"kind": "none"},
                    "compiles": {"exact": len(set(window_lens))}},
            donated_leaves=n_state_leaves,
            compile_count=exe._wstep._cache_size()),
        CompiledProgram.capture(
            f"{tag}/stage", exe._send, sts, ab,
            expect={"collectives": {"kind": "none"},
                    "compiles": {"exact": 1}},
            donated_leaves=n_state_leaves,
            compile_count=exe._send._cache_size()),
    ]
    return programs


def capture_sharded_programs(mcfg, ccfg, mesh, *, policy: str = "replica",
                             I: int = 2, B: int = 8, window_lens=(1, 2),
                             seed: int = 0, tag: str = "sharded"):
    """Registry capture for ``core/coda_sharded.py``: the local-step body
    (communicate=False — R1 collective-free), the window (ONE bucketed
    all-reduce of the documented payload / the int8 all-gather pair), the
    fused overlapped pair (the asserted ring schedule), and the stage
    program (one all-reduce of the stage-dual scalars)."""
    from repro.core import bucketing, coda
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh,
                             policy=policy, donate=True)
    K = ccfg.n_workers
    key = jax.random.PRNGKey(seed)
    st0 = coda.init_state(key, mcfg, ccfg)
    n_state_leaves = len(jax.tree_util.tree_leaves(st0))
    sts = _abstract(st0)
    wb, ab = _mlp_window(mcfg, K, I, B)
    eta = jax.ShapeDtypeStruct((), jnp.float32)
    fls = _fault_vectors(ccfg, K, abstract=True)
    fli = _fault_vectors(ccfg, K, abstract=False)
    masked = fls is not None
    wargs = (sts, wb, eta) if fls is None else (sts, wb, eta, fls)
    wired = bool(exe.worker_axes)        # K=1 degenerate partitions: no wire
    compress = ccfg.avg_compress or None

    if not wired:
        window_expect = {"kind": "none"}
    elif compress == "int8":
        window_expect = {
            "kind": "gather_pair",
            "payload_bytes": coda.window_payload_bytes(st0, "int8",
                                                       masked=masked),
            "n_workers": K}
    else:
        window_expect = {
            "kind": "window",
            "expected_bytes": coda.window_payload_bytes(st0, masked=masked)}
        by_dtype = _payload_by_dtype_or_none(st0, masked=masked)
        if by_dtype:
            window_expect["by_dtype"] = by_dtype
        ob = coda.opt_state_bytes(st0)
        if ob:                           # diagnose an exact-size excess as
            window_expect["opt_bytes"] = ob   # an optimizer-state wire leak

    stage_bytes = coda.stage_payload_bytes(ccfg)
    if wired and stage_bytes:
        stage_expect = {"kind": "window", "expected_bytes": stage_bytes}
    else:
        stage_expect = {"kind": "none"}

    programs = [
        CompiledProgram.capture(
            f"{tag}/local_steps", exe.window_fn(sts, wb, communicate=False),
            *wargs,
            expect={"collectives": {"kind": "none"}},
            donated_leaves=n_state_leaves),
        CompiledProgram.capture(
            f"{tag}/window", exe.window_fn(sts, wb), *wargs,
            expect={"collectives": window_expect},
            donated_leaves=n_state_leaves),
        CompiledProgram.capture(
            f"{tag}/stage", exe.stage_fn(sts, ab), sts, ab,
            expect={"collectives": stage_expect},
            donated_leaves=n_state_leaves),
    ]

    if exe.overlap_pairs:
        wb2 = {"features": jax.ShapeDtypeStruct((2, I, K, B, mcfg.n_features),
                                                jnp.float32),
               "labels": jax.ShapeDtypeStruct((2, I, K, B), jnp.float32)}
        mats, _, _ = bucketing._state_mats(st0)
        if "cv_params" in st0:
            mats = mats * 2              # variates ride the same buckets
        if masked:                       # weight lane(s) ride the f32 bucket
            n_lanes = 2 if "cv_params" in st0 else 1
            mats = mats + [jnp.zeros((K, n_lanes), jnp.float32)]
        ring = exe._ring_spec()
        sizes = bucketing.bucket_sizes(mats)
        n_hops = 2 * bucketing.ring_hop_count(sizes, ring)      # 2 rings/pair
        n_chains = 2 * bucketing.ring_chain_count(sizes, ring)
        if masked:
            v2 = jax.ShapeDtypeStruct((2, K), jnp.float32)
            pargs = (sts, wb2, eta, {"weights": v2, "resync": v2})
        else:
            pargs = (sts, wb2, eta)
        # chain independence needs the local steps to lower as a while loop
        # (I >= 2); an I=1 window inlines and legitimately merges the chains
        programs.append(CompiledProgram.capture(
            f"{tag}/pair", exe.window_pair_fn(sts, wb2), *pargs,
            expect={"collectives": {
                "kind": "ring", "n_hops": n_hops,
                "n_chains": n_chains if I > 1 else None}},
            donated_leaves=n_state_leaves))

    # R4: drive eagerly over repeated + distinct window lengths; the cache
    # behind each (tag, treedef, ndim) entry must hold one executable per
    # distinct shape set and nothing more.  One warmup call first: the
    # explicitly place()d state keys differently from the jit's own output
    # sharding, so the very first dispatch compiles a startup-only variant —
    # the budget pins the steady state after it.
    fkw = {} if fli is None else {"faults": fli}
    st = exe.place(st0)
    st, _ = exe.window_step(
        st, _concrete_window(key, mcfg, K, window_lens[0], B), 0.1, **fkw)
    fn = exe.window_fn(sts, wb)          # same cache entry the drive uses
    fn.clear_cache()
    for wl in tuple(window_lens) + (window_lens[0],):
        wbi = _concrete_window(key, mcfg, K, wl, B)
        st, _ = exe.window_step(st, wbi, 0.1, **fkw)
    n_expected = len(set(window_lens))
    programs.append(CompiledProgram(
        name=f"{tag}/window_cache",
        compile_count=fn._cache_size(),
        expect={"compiles": {"exact": n_expected}}))
    return programs


def capture_training_programs(mcfg, ccfg, *, executor: str = "vmap",
                              mesh=None, policy: str = "replica",
                              I: int = 2, B: int = 8, window_lens=(1, 2),
                              seed: int = 0, tag: str | None = None):
    """Dispatch to the per-executor capture (the registry's training half)."""
    if executor == "vmap":
        return capture_vmap_programs(mcfg, ccfg, I=I, B=B,
                                     window_lens=window_lens, seed=seed,
                                     tag=tag or "vmap")
    if executor == "shard_map":
        if mesh is None:
            raise ValueError("shard_map capture needs a mesh")
        return capture_sharded_programs(mcfg, ccfg, mesh, policy=policy,
                                        I=I, B=B, window_lens=window_lens,
                                        seed=seed, tag=tag or "sharded")
    raise ValueError(f"unknown executor {executor!r}")


# --------------------------------------------------------------------------
# program registry: serving
# --------------------------------------------------------------------------
def capture_serving_programs(cfg=None, *, slots: int = 2, max_len: int = 32,
                             prefill_chunk: int = 4, use_window: bool = True,
                             impl: str = "auto", tag: str = "serve"):
    """Registry capture for ``serving/engine.py``: the two chunk programs
    (C = prefill_chunk for batched chunked prefill, C = 1 for decode-only
    ticks).  Both must be collective-free and host-sync-free; the R4 budget
    is the engine's headline claim — a mixed prefill/decode workload
    compiles EXACTLY those two executables and nothing else."""
    from repro.models import init_params
    from repro.serving import engine as E

    if cfg is None:
        from repro.configs import get_smoke_config
        cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)

    E._chunk_step.clear_cache()
    eng = E.ServingEngine(cfg, params, slots=slots, max_len=max_len,
                          use_window=use_window, impl=impl,
                          prefill_chunk=prefill_chunk)
    # mixed workload: prompts longer than one chunk force prefill ticks AND
    # decode-only ticks (C collapses to 1 once every prompt is consumed)
    for uid in range(slots + 1):
        eng.add_request(E.Request(uid=uid,
                                  prompt=[2 + uid, 3, 4, 5, 6, 7],
                                  max_new_tokens=4))
    eng.run()
    cache_size = E._chunk_step._cache_size()

    cache_s = _abstract(eng.cache)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
    nst = jax.ShapeDtypeStruct((slots,), jnp.int32)
    programs = []
    for C, name in ((prefill_chunk, "prefill_chunk"), (1, "decode_step")):
        toks = jax.ShapeDtypeStruct((slots, C), jnp.int32)
        programs.append(CompiledProgram.capture(
            f"{tag}/{name}", E._chunk_step, cfg, params, cache_s, toks, pos,
            nst, use_window=use_window, impl=impl,
            expect={"collectives": {"kind": "none"}}))
    programs.append(CompiledProgram(
        name=f"{tag}/chunk_step_cache", compile_count=cache_size,
        expect={"compiles": {"exact": 2}}))
    return programs


# --------------------------------------------------------------------------
# program registry: the kernels seam
# --------------------------------------------------------------------------
def capture_kernel_launches(*, impl: str = "auto", shapes=None):
    """Static launch records for every Pallas kernel, computed from the
    ``launch_geometry`` each kernel module launches with (single source of
    truth — the audit cannot drift from the kernel).  ``shapes`` overrides
    the representative problem sizes."""
    from repro.kernels import ops as kops
    from repro.kernels import auc_loss as AK
    from repro.kernels import flash_attention as FK
    from repro.kernels import moe_dispatch as MK
    from repro.kernels import opt_update as OK
    from repro.kernels import prox_update as PK

    s = {"moe": (64, 32, 4, 64), "auc": (300,), "prox": (1000,),
         "prox_tiled": (100352, 2048), "opt": (1000,),
         "flash": (1, 256, 4, 2, 256, 64)}
    s.update(shapes or {})
    _, interpret = kops.dispatch(impl)
    launches = []

    N, K, E, F = s["moe"]
    g = MK.launch_geometry(N, K, E, F)
    launches.append(PallasLaunch(
        kernel="moe_dispatch", grid=g["grid"],
        blocks={"rows": (g["Np"], g["bm"]), "ff": (g["Fp"], g["bn"])},
        alignments={"bm%8": (g["bm"], 8), "bn%128": (g["bn"], 128),
                    "Kp%128": (g["Kp"], 128)},
        interpret=interpret, impl=impl))

    (T,) = s["auc"]
    g = AK.launch_geometry(T)
    launches.append(PallasLaunch(
        kernel="auc_loss", grid=g["grid"], blocks={"t": (g["Tp"], g["bt"])},
        alignments={"bt%128": (g["bt"], 128)},
        interpret=interpret, impl=impl))

    (N,) = s["prox"]
    g = PK.launch_geometry(N, dtypes=(jnp.bfloat16,))
    launches.append(PallasLaunch(
        kernel="prox_update", grid=g["grid"],
        blocks={"n": (g["Np"], g["bt"])},
        alignments={"bt%bf16_rows": (g["bt"], 256)},
        interpret=interpret, impl=impl))

    # rank >= 2 leaves: (rows, lanes) blocks on the leaf's own layout; each
    # minor block is aligned to the tile or spans its whole axis
    g = PK.tiled_geometry(s["prox_tiled"])
    rows, lanes = g["view"][-2:]
    nr, nl = g["grid"][-2:]
    aligned = {}
    if g["rows"] != rows:
        aligned["rows%sublanes"] = (g["rows"], g["sublanes"])
    if g["lanes"] != lanes:
        aligned["lanes%128"] = (g["lanes"], 128)
    launches.append(PallasLaunch(
        kernel="prox_update[tiled]", grid=g["grid"],
        blocks={"rows": (nr * g["rows"], g["rows"]),
                "lanes": (nl * g["lanes"], g["lanes"])},
        alignments=aligned, interpret=interpret, impl=impl))

    (N,) = s["opt"]
    g = OK.launch_geometry(N, dtypes=(jnp.float32, jnp.bfloat16))
    for mode in ("momentum", "precond"):
        launches.append(PallasLaunch(
            kernel=f"opt_update[{mode}]", grid=g["grid"],
            blocks={"n": (g["Np"], g["bt"])},
            alignments={"bt%bf16_rows": (g["bt"], 256)},
            interpret=interpret, impl=impl))

    B, S, nH, KV, Skv, hd = s["flash"]
    g = FK.launch_geometry(B, S, nH, KV, Skv, hd)
    launches.append(PallasLaunch(
        kernel="flash_attention", grid=g["grid"],
        blocks={"q": (S, g["bq"]), "kv": (Skv, g["bk"])},
        interpret=interpret, impl=impl))
    return launches
