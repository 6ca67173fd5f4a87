"""The trainer's step spans: every host stretch of `Trainer.train_step`
is covered by a span nested in one ``train_step`` span, tracing off
records nothing, tracing on turns on no other work (the bytes ledger),
and under a `jax.profiler` session the spans land on the profiler's host
plane with the tracer's durations."""
import jax
import pytest

from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import Tracer, get_tracer, ledger_enabled, set_tracer
from repro.obs import validate_chrome_trace
from repro.obs.trace import _NULL_SPAN
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import single_device_runtime
from repro.train.trainer import Trainer, TrainerConfig


def CHILDREN(fresh):
    """A wave step's direct children of ``train_step``, in order, for
    waves whose programs are ``fresh`` (each wave's ``fresh`` flag): a
    warm wave's ``wave_post`` follows the next wave's span, which
    fetches its loss; a fresh program's and the last wave's follow
    their own."""
    out = ["plan", "grads_init"]
    for i, f in enumerate(fresh):
        late = i > 0 and not fresh[i - 1]
        out += ["materialize", "h2d", "wave"]
        out += ["wave_post"] * (late + (f or i == len(fresh) - 1))
    return out + ["apply", "step_end", "checkpoint"]


def WAVE_CHILDREN(i, fresh):
    """Wave ``i``'s nested spans, in order, as (name, idx): the late
    ``wave_wait`` of wave i-1 after this wave's dispatch, or before a
    fresh program's compile, which dispatches and fetches its own."""
    late = [("wave_wait", i - 1)] if i > 0 and not fresh[i - 1] else []
    if fresh[i]:
        return late + [("compile", i), ("wave_dispatch", i),
                       ("wave_wait", i)]
    own = [("wave_wait", i)] if i == len(fresh) - 1 else []
    return [("wave_dispatch", i)] + late + own


# nested spans and the spans they sit directly in
PARENTS = {"wave_dispatch": ("wave", "compile"),
           "wave_wait": ("wave", "compile"), "compile": ("wave",),
           "apply_dispatch": ("apply",), "apply_fetch": ("apply",)}
WAVE_SPANS = {"materialize", "h2d", "wave", "wave_post", "compile",
              "wave_dispatch", "wave_wait"}
TRAINER_SPANS = {"train_step", "plan", "grads_init", "apply", "step_end",
                 "checkpoint", *WAVE_SPANS, *PARENTS}


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    cfg = get_config("llama3.2-3b").reduced()
    rt = single_device_runtime(remat="none")
    with compat.use_mesh(rt.mesh):
        ds = SyntheticDataset(LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5,
                                                 256),
                              cfg.vocab_size, tokens_per_step=1024,
                              context=512)
        sched = GlobalScheduler(ds, cfg, capacity=256, hdp=1,
                                use_offload=False)
        tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=8), sched,
                     TrainerConfig(capacity=256, calibrate=False,
                                   ckpt_every=1,
                                   ckpt_dir=str(tmp_path_factory.mktemp(
                                       "ckpt"))))
        yield tr
        tr.sched.stop()
        tr.ckpt.wait()


@pytest.fixture
def tracer():
    prev = get_tracer()
    t = set_tracer(Tracer(enabled=True))
    yield t
    set_tracer(prev)


def _traced_step(trainer, tracer):
    step = trainer.step
    with compat.use_mesh(trainer.rt.mesh):
        trainer.train_step()
    return step, [e for e in tracer.snapshot() if e["ph"] == "X"]


def _tree(spans):
    """[(span, parent or None, direct children)] by interval nesting."""
    order = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
    stack, out = [], []
    for e in order:
        while stack and e["ts"] >= stack[-1][0]["ts"] + stack[-1][0]["dur"]:
            stack.pop()
        node = (e, stack[-1][0] if stack else None, [])
        if stack:
            stack[-1][2].append(e)
        stack.append(node)
        out.append(node)
    return out


def test_train_step_spans_cover_the_step(trainer, tracer):
    assert not ledger_enabled()
    step, spans = _traced_step(trainer, tracer)    # fresh: compiles
    doc = tracer.to_chrome()
    ok, problems = validate_chrome_trace(doc,
                                         require_names=tuple(TRAINER_SPANS))
    assert ok, problems
    tree = _tree(spans)
    (top, _, children), = [n for n in tree if n[0]["name"] == "train_step"]
    waves = [n for n in tree if n[0]["name"] == "wave"]
    fresh = [e["args"]["fresh"] for e, _, _ in waves]
    assert any(fresh) and not all(fresh), fresh   # both kinds of wave
    assert [c["name"] for c in children] == CHILDREN(fresh)
    for i, (e, _, _) in enumerate(waves):
        assert e["args"]["idx"] == i, e
        nested = [n for n in tree if e["ts"] <= n[0]["ts"] < e["ts"]
                  + e["dur"] and n[0] is not e]
        assert [(n[0]["name"], n[0]["args"]["idx"]) for n in nested] \
            == WAVE_CHILDREN(i, fresh), (i, nested)
    assert [c["args"]["idx"] for c in children
            if c["name"] == "wave_post"] == list(range(len(waves)))
    for e, parent, _ in tree:
        if e["name"] not in TRAINER_SPANS:
            continue                  # the scheduler's own, inside plan
        assert e["args"]["step"] == step, e
        if e["name"] in WAVE_SPANS:
            assert "idx" in e["args"], e
        if e["name"] in PARENTS:
            assert parent["name"] in PARENTS[e["name"]], (e, parent)
        elif e is not top:
            assert parent is top, (e, parent)
    self_us = top["dur"] - sum(c["dur"] for c in children)
    assert self_us < 0.05 * top["dur"], (self_us, top["dur"])
    # tracing turns on no other work: no ledger, no ledger stamps
    assert trainer.ledger is None
    assert not any("bytes_pred" in e.get("args", {}) for e in spans)


def test_untraced_step_records_nothing(trainer):
    prev = get_tracer()
    off = set_tracer(Tracer(enabled=False))
    try:
        assert off.span("wave", step=0) is _NULL_SPAN
        assert off.step_span("train_step", 0, "train") is _NULL_SPAN
        with compat.use_mesh(trainer.rt.mesh):
            trainer.train_step()
    finally:
        set_tracer(prev)
    assert off.snapshot() == []


def test_spans_reach_the_profilers_host_plane(trainer, tracer, tmp_path):
    from jax.profiler import ProfileData
    with compat.use_mesh(trainer.rt.mesh):
        trainer.train_step()                 # warm: nothing compiles below
    tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, spans = _traced_step(trainer, tracer)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns / 1e3))
    # the step span is the profiler's step "train" (its Steps line)
    names = {"train_step": "train"}
    for name in {e["name"] for e in spans}:
        ours = sorted((e["ts"], e["dur"]) for e in spans
                      if e["name"] == name)
        theirs = sorted(host.get(names.get(name, name), []))
        assert len(theirs) == len(ours), (name, len(theirs), len(ours))
        for (_, dur), (_, pdur) in zip(ours, theirs):
            assert abs(pdur - dur) <= max(50.0, 0.05 * dur), \
                (name, dur, pdur)
