"""Chip 0's idle time per step during which the host was in its own code.

The idle stretches of the window (the window less the union of chip 0's
op intervals) are cut at every boundary of the program's spans, as the
trace places them on the device's clock; each piece goes to the
innermost span that covers it.  Pieces under ``wave_wait``,
``apply_fetch`` or ``compile`` (the host waits on the chip or the
compiler) and pieces under no span do not count; the rest is time the
chip waited on the host.  Nothing to read without a ``wave`` or
``round`` span."""
import numpy as np

import devtrace

WAITING = ("wave_wait", "apply_fetch", "compile")
UNCOVERED = None


def idle_by_span(trace):
    """{innermost span name, or None where no span covers: chip-0 idle ns}
    over the window."""
    w0, w1 = trace.window
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in trace.host_spans
             if min(e, w1) > max(s, w0)]
    cuts = np.unique(np.array([w0, w1] + [t for _, s, e in spans
                                          for t in (s, e)], np.float64))
    # innermost = shortest covering span: paint longest first
    owner = np.full(len(cuts) - 1, -1)
    for k in sorted(range(len(spans)),
                    key=lambda k: spans[k][1] - spans[k][2]):
        _, s, e = spans[k]
        owner[np.searchsorted(cuts, s):np.searchsorted(cuts, e)] = k
    idle = np.diff(_idle_before(trace, cuts))
    out = {}
    for k, t in zip(owner, idle):
        if t > 0:
            name = spans[k][0] if k >= 0 else UNCOVERED
            out[name] = out.get(name, 0.0) + float(t)
    return out


def _idle_before(trace, ts):
    """Chip 0's idle ns in [window start, t) for each t of sorted ``ts``."""
    w0, w1 = trace.window
    busy = devtrace.clip(devtrace.union(trace.chips[0].iv), w0, w1)
    if not len(busy):
        return ts - w0
    done = np.concatenate([[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])
    k = np.searchsorted(busy[:, 0], ts, side="right")
    inside = np.where(k > 0, np.clip(ts - busy[np.maximum(k - 1, 0), 1],
                                     None, 0.0), 0.0)
    return (ts - w0) - (done[k] + inside)


def read(ctx):
    trace = ctx["trace"]
    if not any(n in ("wave", "round") for n, _, _ in trace.host_spans):
        return None
    idle = idle_by_span(trace)
    host = sum(t for name, t in idle.items()
               if name is not UNCOVERED and name not in WAITING)
    return host / 1e6 / len(ctx["steps"])
