"""Host time per step in the program's ``apply`` span (the AdamW update,
optim/adamw.py, ending in the host fetch of its summary), mean over the
window's steps."""


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"
             and e["name"] == "apply"]
    if not spans:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / len(ctx["steps"])
