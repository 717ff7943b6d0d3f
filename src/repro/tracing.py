"""The names of the training path's profiler spans and device scopes, and
the counter of backend compiles.

Host spans: ``coda.fit`` opens these with ``jax.profiler.TraceAnnotation``
(``StepTraceAnnotation`` for the window).  They cost a context manager each
and write nothing unless a profiler is running (``jax.profiler.trace`` or a
capture through ``jax.profiler.start_server``); then they land on the
``/host:CPU`` plane, on the same clock as each device's ``XLA Ops`` line.

* ``coda.window`` — one iteration of fit's window loop (a fused pair
  included), with ``step_num`` = the global window counter at its start;
  nested inside it: ``coda.sample`` (the caller's ``sample_window``),
  ``coda.dispatch`` (the executor's window call: the enqueue, and a compile
  when one happens), ``coda.sync`` (the host waiting for the window's loss),
  ``coda.eval`` and ``coda.ckpt``.  The window's own time outside its
  children is the key split and the loop's bookkeeping.
* ``coda.stage_end`` — the stage boundary's draw and call.

Device scopes: ``jax.named_scope`` names at each layer's seam.  A scope
changes only the ``op_name`` metadata of the HLO it encloses (a path such as
``jit(body)/window/while/body/closed_call/local_step/vmap(jvp(model))/conv``),
never the program the chip runs; ``scope_of`` reads the scope back.
"""
from __future__ import annotations

import re

WINDOW = "coda.window"
SAMPLE = "coda.sample"
DISPATCH = "coda.dispatch"
SYNC = "coda.sync"
EVAL = "coda.eval"
CKPT = "coda.ckpt"
STAGE_END = "coda.stage_end"
HOST_SPANS = (WINDOW, SAMPLE, DISPATCH, SYNC, EVAL, CKPT, STAGE_END)

# device scopes, outermost first: the window body, one local step, the
# model's forward (its backward inherits the scope), the objective's loss,
# the primal update (flatten, pad, kernel, slice, reshape back), the dual
# step, the cross-worker averaging (packing, all-reduce or ring,
# unpacking) and the stage boundary
SCOPES = ("window", "local_step", "model", "auc_loss", "update", "dual_step",
          "average", "stage_end")
# scopes inside the model, opened where a model has the layer: attention
# (``models/attention.attend``), the mixture of experts
# (``models/moe.apply_moe``) and, inside it, the sorted dispatch's run at
# its full row bound, taken only when more rows are routed to the held
# experts than the tight bound holds; an MLP model has none
FULL_BOUND = "moe_full_bound"
MODEL_SCOPES = ("attention", "moe", FULL_BOUND)

# a path component names a scope bare (``update``) or wrapped in the
# transforms applied to it (``vmap(transpose(jvp(model)))``); a ``jit(...)``
# component names a jitted function, never a scope
_COMPONENT = re.compile(r"(?:(?!jit\()\w+\()*(\w+)\)*")


def scope_of(op_name: str) -> str:
    """The deepest component of an HLO op's ``op_name`` path that is one of
    ``SCOPES`` or ``MODEL_SCOPES``, or ``"other"``."""
    for part in reversed(op_name.split("/")):
        m = _COMPONENT.fullmatch(part)
        if m and m.group(1) in SCOPES + MODEL_SCOPES:
            return m.group(1)
    return "other"


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts the backend compilations JAX reports while it is open, and
    sums their seconds."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)
