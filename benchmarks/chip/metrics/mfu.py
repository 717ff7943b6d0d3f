"""Model FLOP/s utilisation of the whole CoDA step: the configuration's
forward plus backward FLOPs per sample (recomputation not counted) times
the samples trained per second, over the chips' bf16 peak, in percent."""


def read(run):
    rate = run.flops_per_sample * run.samples / run.seconds
    return 100.0 * rate / (run.cell.chips * run.peaks["bf16_flops_per_s"])
