"""Host time per step in the program's ``plan`` span (the scheduler:
sched/service.py, core/planner.py), mean over the window's steps."""


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"
             and e["name"] == "plan"]
    if not spans:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / len(ctx["steps"])
