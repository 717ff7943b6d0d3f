"""Compiled-artifact analysis: collective bytes from optimized HLO text and
the three roofline terms (see docs/analysis.md).

collective_bytes is NOT in cost_analysis(); we parse the optimized HLO and
sum the result-shape bytes of every cross-device op.  ``collective_ops``
keeps the per-op records (kind, per-dtype bytes, replica groups) so tests
can verify the *count* and *payload dtype* of what actually crosses the
wire — e.g. that one CoDA window lowers to exactly one all-reduce of
``model_bytes`` operand bytes, or that the int8-compressed averaging ships
an s8 payload (tests/test_coda_sharded.py).

The expected payloads come from the generic tree accounting
(``coda.model_bytes`` / ``coda.window_payload_by_dtype``: every params leaf
+ every leaf of the objective's dual tree, core/objective.py) — nothing
here or there names a dual field, so the asserts hold for any registered
objective's layout (AUC's 3 scalars, pAUC-DRO's 4, BCE's none).
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# Matches the op name right after the result type.  The optional "-start"
# suffix is captured so async collectives count ONCE, from their start op:
# the matching "-done" line does not match at all (the regex requires "("
# directly after the op name / "-start", and "-done(" has neither) — a
# property tests/test_hlo_parser.py pins.  Tuple result types may nest
# parens (multi-operand async collectives), hence the non-greedy paren
# matcher with a bounded nesting depth of one.
_OP_RE = re.compile(
    r"=\s*(?P<type>\((?:[^()]|\([^()]*\))*\)|[\w\[\],]+(?:\{[^}]*\})?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(")


_GROUPS_RE = re.compile(r"replica_groups=(\{\{.*?\}\}|\{[^{}]*\})")


def _dtype_bytes(type_str: str) -> dict[str, int]:
    out: dict[str, int] = {}
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[dt] = out.get(dt, 0) + n * _DTYPE_BYTES[dt]
    return out


def _shape_bytes(type_str: str) -> int:
    return sum(_dtype_bytes(type_str).values())


def _tuple_components(type_str: str) -> list[str]:
    """Split a tuple type string at its TOP-LEVEL commas — one nesting level
    deep, matching _OP_RE's type matcher.  Non-tuple types come back as a
    single component."""
    s = type_str.strip()
    if not (s.startswith("(") and s.endswith(")")):
        return [s]
    parts, depth, cur = [], 0, []
    for ch in s[1:-1]:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def collective_ops(hlo_text: str) -> list[dict]:
    """One record per collective op in the optimized HLO:
    {op, bytes, by_dtype, components, replica_groups}.  ``bytes`` are
    result-shape bytes (== per-participant operand bytes for all-reduce; the
    gathered size for all-gather).  ``components`` holds one per-dtype byte
    dict per result tuple element: XLA's all-reduce combiner may fuse
    several buckets into one tuple-shaped op, and each element is still one
    bucket.  ``replica_groups`` is the literal group string, so callers can
    tell cross-worker reductions apart from any intra-group ones.

    Async pairs count ONCE: the ``-start`` op is the record (only the
    RESULT component of its (operands, results) tuple type is summed — the
    operand alias would double the bytes) and the ``-done`` line never
    matches."""
    ops = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        g = _GROUPS_RE.search(line)
        type_str = m.group("type")
        if m.group("start"):
            parts = _tuple_components(type_str)
            if len(parts) >= 2:
                type_str = parts[1]
        by_dtype = _dtype_bytes(type_str)
        ops.append({
            "op": m.group("op"),
            "bytes": sum(by_dtype.values()),
            "by_dtype": by_dtype,
            "components": [_dtype_bytes(c) for c in _tuple_components(type_str)],
            "replica_groups": g.group(1) if g else "",
        })
    return ops


def verify_window_payload(hlo_text: str, expected_bytes: int, *,
                          op: str = "all-reduce",
                          count: int = None,
                          by_dtype: dict[str, int] = None,
                          baseline_bytes: int = None,
                          delta_bytes: int = None,
                          opt_bytes: int = None) -> list[dict]:
    """Assert a compiled CoDA/CODASCA window's wire traffic: all collectives
    are of kind ``op``, totalling ``expected_bytes`` result-shape bytes —
    and *no other* collective of any kind.

    The bucketed averaging ships ONE collective per payload *dtype bucket*
    (core/bucketing.pmean_buckets).  ``expected_bytes`` is always the
    LOGICAL payload (``coda.window_payload_bytes``: ``model_bytes`` for a
    CoDA window, ``2 ×`` that for CODASCA — state + control variates in
    one bucket).

    Three modes:
      * default (``count=None``, no ``by_dtype``) — every payload dtype
        appears in exactly one op and the wire bytes equal
        ``expected_bytes``.  The right check for single-dtype states (one
        all-reduce, exactly).
      * ``count=N`` — pin the op count instead, wire bytes still equal
        ``expected_bytes``.
      * ``by_dtype={hlo tag: bytes}`` (``coda.window_payload_by_dtype``) —
        the mixed-dtype check: each logical bucket must map to exactly one
        op result (a whole op, or one element of a tuple-shaped op the
        all-reduce combiner fused), either verbatim or *float-normalized*
        (backends without native low-precision collectives, e.g. the CPU
        host backend, widen a bf16/f16 all-reduce to f32 — same element
        count, doubled wire bytes), no result may be left over, and the
        buckets must sum to ``expected_bytes``.

    ``baseline_bytes``/``delta_bytes`` (always both) additionally pin the
    payload as an exact baseline + feature delta: ``expected_bytes`` must
    equal their sum.  This is the streaming-eval assert — with the sketch
    hook off the compiled wire bytes are the baseline *unchanged*
    (``delta_bytes=0``), with it on they grow by exactly
    ``coda.streaming_payload_bytes(state)`` (2·stream_bins·4 fp32) and not
    a byte more, while the op-shape checks above still hold (the sketch
    rides the existing fp32 bucket, it does not add a collective).

    ``opt_bytes`` (``coda.opt_state_bytes``): per-worker local-optimizer
    state size.  It never changes what passes — preconditioning is strictly
    local and the state must stay off the wire — but when the shipped bytes
    exceed the expectation by exactly this amount, the failure message says
    "optimizer state leaked onto the wire" instead of a raw byte delta.

    Returns the op records on success so callers can additionally inspect
    dtypes / replica groups.

    This is the R1 collective-placement rule of the compiled-program
    auditor — the checker lives in ``analysis/audit.py``
    (``window_payload_problems``); this wrapper keeps the historical
    assert-style entry point.
    """
    from repro.analysis import audit
    return audit.assert_window_payload(
        hlo_text, expected_bytes, op=op, count=count, by_dtype=by_dtype,
        baseline_bytes=baseline_bytes, delta_bytes=delta_bytes,
        opt_bytes=opt_bytes)


_DOT_RE = re.compile(r"\b(dot|convolution)\(")
_CALLEE_RE = re.compile(r"(?:calls|body|condition|to_apply)=(%?[\w.\-]+)")
# computation headers: "%name (params...) -> type {" / "ENTRY %name (...)";
# the param list may nest parens (tuple types), so don't try to match it
_COMPUTATION_HDR_RE = re.compile(
    r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*->.*\{\s*$")


def _dot_bearing_computations(hlo_text: str):
    """Names of HLO computations that contain a dot/convolution, directly or
    through any computation they call (fusions, while bodies — the scanned
    local steps live inside a while loop).  This is how 'real model
    compute' is told apart from the ring's own index arithmetic."""
    direct, calls, cur = set(), {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_HDR_RE.match(line)
        if m and "{" in line:
            cur = m.group(1).lstrip("%")
            continue
        if cur is None:
            continue
        if _DOT_RE.search(line):
            direct.add(cur)
        for callee in _CALLEE_RE.findall(line):
            calls.setdefault(cur, set()).add(callee.lstrip("%"))
    # propagate dot-ness up the call graph to a fixed point
    changed = True
    while changed:
        changed = False
        for name, callees in calls.items():
            if name not in direct and callees & direct:
                direct.add(name)
                changed = True
    return direct


_SSA_NAME_RE = re.compile(r"(%[\w.\-]+)")


def permute_chain_components(hlo_text: str) -> int:
    """Number of INDEPENDENT collective-permute dependency chains in the
    entry computation — the falsifiable core of the overlap claim.

    Two permutes belong to one chain when one's result feeds the other
    through entry-computation dataflow (adds, fusions, slices — the ring's
    glue ops); propagation is cut at ``while``/``conditional`` calls, which
    are the window boundaries (the next window's scan consumes the whole
    averaged state, so every ring of the next window would otherwise
    spuriously merge with every ring of the previous one).  The chunked
    ring lowering must produce exactly ``bucketing.ring_chain_count``
    components per ring: a de-chunked lowering collapses them to one per
    bucket, and an artificial cross-chunk dependency (which would
    serialize the chunks and kill the overlap) merges components.

    Only meaningful when the local steps lower as a loop (I ≥ 2): an I=1
    window inlines its compute into the entry computation, and the ring-
    to-ring dependency through the inlined (dot-free, elementwise) prox
    updates legitimately merges every component into one — callers skip
    the chain check there (``verify_overlapped_window(n_chains=None)``).
    """
    lines = hlo_text.splitlines()
    start = next((i for i, ln in enumerate(lines)
                  if ln.startswith("ENTRY ")), None)
    if start is None:
        raise AssertionError("no ENTRY computation in HLO text")
    carried: dict[str, frozenset] = {}
    parent: dict[int, int] = {}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    n_roots = 0
    for raw in lines[start + 1:]:
        s = raw.strip()
        if s == "}":
            break
        if not s.startswith("%") or "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        name = lhs.strip().split()[0]
        ancestors = set()
        if " while(" not in s and " conditional(" not in s:
            for ref in _SSA_NAME_RE.findall(rhs):
                ancestors |= carried.get(ref, frozenset())
        if _OP_RE.search(s):                  # a collective-permute hop
            if not ancestors:
                rid = n_roots
                parent[rid] = rid
                n_roots += 1
            else:
                ids = {find(i) for i in ancestors}
                rid = ids.pop()
                for other in ids:
                    parent[find(other)] = find(rid)
            carried[name] = frozenset({rid})
        elif ancestors:
            carried[name] = frozenset(ancestors)
    return len({find(r) for r in range(n_roots)})


def verify_overlapped_window(hlo_text: str, *, n_hops: int,
                             n_chains: int = None,
                             require_compute_between: bool = True) -> list[dict]:
    """Assert the overlapped window-pair module's wire schedule: NO blocking
    all-reduce (or any other collective kind); the averaging is exactly
    ``n_hops`` ``collective-permute`` ops (C chunk chains × 2·(R−1) hops ×
    the rings in the module, from ``bucketing.ring_hop_count``); and, with
    ``n_chains`` (rings × ``bucketing.ring_chain_count``), that the hops
    form exactly that many INDEPENDENT dependency chains — the property
    that lets an async scheduler run late chunks' wire time under the
    compute consuming early chunks.  A de-chunked or artificially
    serialized lowering fails the chain check even though its hop count
    may survive.

    ``require_compute_between`` additionally checks that dot-bearing
    compute (the second window's matmuls) is scheduled between the first
    and last hop.  For a two-ring pair module this is a structural sanity
    check (it confirms both windows really were fused into one module
    around the averaging) rather than a scheduling guarantee — the
    falsifiable overlap invariants are the chain/hop/no-barrier checks
    above.  Returns the permute op records.

    This is the ring form of the auditor's R1 collective-placement rule —
    the checker lives in ``analysis/audit.py``
    (``overlapped_window_problems``); this wrapper keeps the historical
    assert-style entry point.
    """
    from repro.analysis import audit
    return audit.assert_overlapped_window(
        hlo_text, n_hops=n_hops, n_chains=n_chains,
        require_compute_between=require_compute_between)


def collective_bytes(hlo_text: str) -> dict[str, dict]:
    """Per-collective-kind {bytes, count, by_dtype} from optimized HLO."""
    out = {k: {"bytes": 0, "count": 0, "by_dtype": {}} for k in _COLLECTIVES}
    for rec in collective_ops(hlo_text):
        kind = out[rec["op"]]
        kind["bytes"] += rec["bytes"]
        kind["count"] += 1
        for dt, b in rec["by_dtype"].items():
            kind["by_dtype"][dt] = kind["by_dtype"].get(dt, 0) + b
    out["total_bytes"] = sum(v["bytes"] for v in out.values() if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for v in out.values() if isinstance(v, dict))
    return out


# --------------------------------------------------------------------------
# roofline
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Hardware:
    """v5e-class chip (the production target)."""
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link (~3 links usable/chip)


V5E = Hardware()


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   n_chips: int, hw: Hardware = V5E) -> dict:
    """The three §Roofline terms, in seconds.

    flops / hbm_bytes are whole-program HLO numbers (cost_analysis of the
    partitioned module is already per-device under GSPMD; we pass
    per_device=True from the dry-run and n_chips=1 here accordingly —
    see launch/dryrun.py).
    """
    compute = flops / (n_chips * hw.peak_flops)
    memory = hbm_bytes / (n_chips * hw.hbm_bw)
    collective = coll_bytes / (n_chips * hw.ici_bw)
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k]).replace("_s", "")
    return terms
