"""Host time per step spent enqueuing programs onto the chip: the
program's ``grads_init`` (the zero gradient accumulator, one small
program a leaf), ``wave_dispatch`` (each wave's or round's jitted call, up
to its return) and ``apply_dispatch`` (the optimizer's) spans, mean over
the window's steps.  Nothing to read without a ``wave`` or ``round``
span; a recording without those three spans reads 0."""
PARTS = ("grads_init", "wave_dispatch", "apply_dispatch")


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"]
    if not any(e["name"] in ("wave", "round") for e in spans):
        return None
    return sum(e["dur"] for e in spans
               if e["name"] in PARTS) / 1e3 / len(ctx["steps"])
