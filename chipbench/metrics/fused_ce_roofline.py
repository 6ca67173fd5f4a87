"""The fused cross-entropy kernel's share of its roofline: the least time
the chip needs for softmax cross-entropy over the window's tokens and the
vocabulary slice (chipbench/flops.py) over the kernel's device time."""
import flops


def read(ctx):
    t = ctx["trace"].kernel_s("fused_ce")
    if t <= 0:
        return None
    c, peak = ctx["config"], ctx["peak"]
    tokens = sum(ctx["step_tokens"])
    need = max(flops.ce_flops(c, tokens) / peak["bf16_flops"],
               flops.ce_bytes(c, tokens) / peak["hbm_bytes_per_s"])
    return 100.0 * need / t
