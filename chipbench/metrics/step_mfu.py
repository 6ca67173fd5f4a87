"""The whole step's share of the chips' bf16 peak while they work: model
operations of the window's documents (chipbench/flops.py, no
recomputation) over chips x peak x the device's busy time in the trace
(the union of its op intervals, mean over chips)."""
import flops


def read(ctx):
    c, peak = ctx["config"], ctx["peak"]
    work = sum(flops.model_flops(c, ls) for ls in ctx["step_lengths"])
    return 100.0 * work / (ctx["chips"] * peak["bf16_flops"]
                           * ctx["trace"].busy_s)
