"""Host time per step in the data loader: the program's ``materialize``
spans (the wait for a wave's host buffers: data/loader.py, the
sched/service.py materializer) and ``h2d`` spans (the trainer's copy of
them to the device), mean over the window's steps.  Nothing to read
without a ``wave`` or ``round`` span; a recording without ``h2d`` spans
reads the ``materialize`` time alone."""
PARTS = ("materialize", "h2d")


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"]
    if not any(e["name"] in ("wave", "round") for e in spans):
        return None
    return sum(e["dur"] for e in spans
               if e["name"] in PARTS) / 1e3 / len(ctx["steps"])
