"""The step loop's readers (`input_wait_ms`, `dispatch_ms`,
`host_bound_idle_ms`) on a synthetic trace whose idle stretches and
spans are known: a gap under nested spans, a gap under no span, and a
gap split across two spans."""
import numpy as np
import pytest

import devtrace
import run

US = 1e3                                  # ns per µs

# chip 0 busy in [0, 100), [200, 300), [500, 600), [800, 1000) µs: idle
# [100, 200) under wave > {wave_dispatch, wave_wait}, [300, 500) under
# no span, [600, 800) split between h2d and wave_post
SPANS = [("grads_init", 10, 40), ("materialize", 40, 60), ("h2d", 60, 90),
         ("wave", 90, 260), ("wave_dispatch", 90, 120),
         ("wave_wait", 120, 250), ("materialize", 540, 550),
         ("h2d", 550, 700), ("wave_post", 700, 850),
         ("apply", 860, 990), ("apply_dispatch", 860, 880),
         ("apply_fetch", 880, 990)]
BUSY = [(0, 100), (200, 300), (500, 600), (800, 1000)]


def _ctx(spans=SPANS, steps=1):
    iv = np.array(BUSY, np.float64) * US
    chip = devtrace.ChipOps(["op"] * len(iv), ["fusion"] * len(iv),
                            [None] * len(iv), iv)
    trace = devtrace.Trace([chip], (0.0, 1000 * US),
                           [(n, s * US, e * US) for n, s, e in spans])
    chrome = [{"name": n, "ph": "X", "ts": float(s), "dur": float(e - s)}
              for n, s, e in spans]
    return {"trace": trace, "spans": chrome, "steps": list(range(steps))}


def _read(name, ctx):
    return run.load_reader(name)(ctx)


def test_idle_goes_to_the_innermost_span():
    idle = _host_bound().idle_by_span(_ctx()["trace"])
    assert idle == pytest.approx({"wave_dispatch": 20 * US,
                                  "wave_wait": 80 * US, None: 200 * US,
                                  "h2d": 100 * US, "wave_post": 100 * US})


@pytest.mark.parametrize("steps", [1, 2])
def test_readers_per_step(steps):
    ctx = _ctx(steps=steps)
    # host-bound: wave_dispatch 20 + h2d 100 + wave_post 100 µs; the
    # wait under wave_wait and the stretch under no span do not count
    assert _read("host_bound_idle_ms", ctx) == pytest.approx(0.22 / steps)
    # materialize 20 + 10, h2d 30 + 150 µs
    assert _read("input_wait_ms", ctx) == pytest.approx(0.21 / steps)
    # grads_init 30, wave_dispatch 30, apply_dispatch 20 µs
    assert _read("dispatch_ms", ctx) == pytest.approx(0.08 / steps)


def test_readers_need_a_wave():
    ctx = _ctx([s for s in SPANS if s[0] not in ("wave", "round")])
    for name in ("input_wait_ms", "dispatch_ms", "host_bound_idle_ms"):
        assert _read(name, ctx) is None, name


def test_missing_spans_read_zero():
    """A recording from before the step spans: only wave and apply."""
    ctx = _ctx([s for s in SPANS if s[0] in ("wave", "apply")])
    assert _read("dispatch_ms", ctx) == 0
    assert _read("input_wait_ms", ctx) == 0
    # the whole gap under wave (100 µs) and none under apply
    assert _read("host_bound_idle_ms", ctx) == pytest.approx(0.1)


def _host_bound():
    import importlib.util
    path = run.ROOT / "chipbench" / "metrics" / "host_bound_idle_ms.py"
    spec = importlib.util.spec_from_file_location("host_bound_idle_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
