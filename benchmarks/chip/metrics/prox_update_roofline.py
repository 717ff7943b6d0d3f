"""Share of its roofline that the proximal update reaches, in percent.

The least time is the bytes the update needs over the chip's peak HBM
bandwidth: for every parameter of every worker and every local step, v, g
and v0 read and v written, 4 bytes each.  That count is of the work, not of
the kernels that do it, so it holds however many calls carry it.  The time
is the summed device time of the update's Pallas kernel in the trace.

The kernel's time is HBM time only where its operands live in HBM.  XLA's
memory-space assignment may place a leaf's operands in VMEM (``S(1)`` in
the compiled HLO) and move them with async copies that overlap other
operations; the kernel then reads VMEM and its time no longer bounds the
HBM bytes.  So the share is listed only for cells whose compiled window
keeps nearly all of the update's bytes in HBM (StableLM-2: 96.5%).  In the
ResNet-50 windows 69% (one chip) and 99% (four chips) sit in VMEM, and the
share read 55% and 141% there.
"""

import trace_reduce

# the prox_update kernel's operations in the trace
KERNEL = r"prox_update"
BYTES_PER_PARAM = 16


def read(run):
    if run.trace is None:
        return None
    ns = sum(trace_reduce.kernel_ns(run.trace, c, KERNEL)
             for c in run.trace.chips())
    if ns <= 0:
        return None
    need = (BYTES_PER_PARAM * run.params_per_worker * run.cell.K * run.steps)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (ns / 1e9)
