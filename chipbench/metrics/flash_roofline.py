"""The flash attention kernels' share of their roofline: the least time
the chip needs for causal attention over the window's documents (the
larger of operations over peak and bytes over bandwidth, chipbench/
flops.py) over the kernels' device time in the trace."""
import flops


def read(ctx):
    t = ctx["trace"].kernel_s("flash")
    if t <= 0:
        return None
    c, peak = ctx["config"], ctx["peak"]
    docs = [n for ls in ctx["step_lengths"] for n in ls]
    need = max(flops.attention_flops(c, docs) / peak["bf16_flops"],
               flops.attention_bytes(c, docs) / peak["hbm_bytes_per_s"])
    return 100.0 * need / t
