"""Share of the bf16 roofline that the grouped expert GEMM kernels reach,
in percent.

The work counted is that of the MoE layers' expert products at the load an
even router gives the held experts: per MoE layer and local step,
T k E_held / E rows (T the step's tokens), each through 9 GEMM passes of
2 x hidden x expert-width FLOPs (3 forward: gate, up, down; 6 backward: dx
and dw of each).  The least time is those FLOPs over the chip's bf16 peak
(the kernels are compute-bound at these widths: each expert's weights are
read once per pass).  The time is the summed device time of every
operation whose name starts with ``grouped_matmul`` (the forward and dx
kernel, ``grouped_matmul``, and the dw kernel, ``grouped_matmul_wgrad``).

Two biases, both recorded in PERF.md.  The layers are rematerialised, so
the kernels run the 3 forward passes twice while the work counts them
once: the share reads at most 9/12 of what the kernels reach on the work
they do.  The rows are the even-router expectation, not the routed count;
PERF.md gives the counts the check's first batch routes to each layer's
held experts against that expectation.  The kernels' time also holds the
grid steps past the live tiles (skipped, each a fixed launch cost).
"""

import trace_reduce

KERNEL = r"^grouped_matmul"
GEMM_PASSES = 9


def read(run):
    if run.trace is None:
        return None
    ns = sum(trace_reduce.kernel_ns(run.trace, c, KERNEL)
             for c in run.trace.chips())
    if ns <= 0:
        return None
    cfg = run.cell.cfg
    rows = (run.cell.B * run.cell.traffic["seq_len"]
            * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    flops = (GEMM_PASSES * 2 * rows * cfg["hidden_size"]
             * cfg["moe_intermediate_size"] * layers
             * run.cell.K * run.steps)
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / (ns / 1e9)
