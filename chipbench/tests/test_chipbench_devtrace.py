"""The trace reducer and the per-layer readers on a trace recorded on a
TPU v5e (a one-step window of `dsllm7b.github2k.1chip`: the gzipped
`.xplane.pb` of a ``--trace 1`` run, and beside it the run's seed, steps,
step starts on the host clock and the program's spans), and the FLOP and
byte counts they use."""
import gzip
import json

import numpy as np
import pytest

import devtrace
import flops
import run
from tinycell import HERE
from model import load_config
from peaks import peaks
from traffic import Traffic, load_mix

DATA = HERE / "data"


def recorded_ctx():
    """The reader context of the recorded run, rebuilt from its files."""
    side = json.loads((DATA / "onechip_window.spans.json").read_text())
    _, cell = run.load_cell(side["workload"])
    c = load_config(cell["config"])
    traffic = Traffic(load_mix(cell["traffic"]), c["vocab_size"],
                      side["seed"])
    lengths = [traffic.step_lengths(s) for s in side["steps"]]
    xspace = gzip.decompress((DATA / "onechip_window.xplane.pb.gz")
                             .read_bytes())
    trace = devtrace.read(xspace, c["vocab_size"], cell["chips"],
                          side["step_starts"], side["spans"])
    return {"config": c, "peak": peaks("TPU v5 lite"), "chips": 1,
            "trace": trace, "spans": side["spans"], "steps": side["steps"],
            "step_lengths": lengths,
            "step_tokens": [sum(ls) for ls in lengths],
            "compiles_in_window": side["compiles_in_window"]}


@pytest.fixture(scope="module")
def ctx():
    return recorded_ctx()


def test_busy_and_window(ctx):
    t = ctx["trace"]
    assert len(t.chips) == 1
    assert 0.5 < t.window_s < 1.0
    assert 0.7 * t.window_s < t.busy_s < t.window_s


def test_kernels_found_by_kind(ctx):
    t = ctx["trace"]
    flash, ce = t.kernel_s("flash"), t.kernel_s("fused_ce")
    assert 0.05 * t.window_s < flash < 0.5 * t.window_s
    assert 0 < ce < flash
    assert t.kernel_s("other_kernel") == 0


def test_breakdown_lists_ops_and_labelled_gaps(ctx):
    b = ctx["trace"].breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    labels = {name for name, _ in b["idle_gaps"]}
    assert labels & {"apply", "materialize", "wave", "plan"}


def test_readers(ctx):
    bench, cell = run.load_cell("dsllm7b.github2k.1chip")
    m = {k: v["value"] for k, v in run.per_layer(bench, cell, ctx).items()}
    assert set(m) == {p["name"] for p in run.cell_metrics(
        bench, cell, "per_layer")}
    assert 0 < m["device_idle_share"] < 30
    assert 0 < m["flash_roofline"] < 100 and 0 < m["fused_ce_roofline"] < 100
    assert 20 < m["step_mfu"] < 60
    assert m["compiles_in_window"] == 0
    assert m["plan_ms"] > 0 and m["apply_ms"] > 0


def test_interval_arithmetic():
    iv = np.array([[0, 10], [5, 15], [20, 30]], float)
    assert devtrace.length(devtrace.union(iv)) == 25
    assert devtrace.length(devtrace.clip(devtrace.union(iv), 8, 25)) == 12


def test_op_names_parse():
    name = ('%fusion.349 = bf16[2048,11008]{1,0:T(8,128)(2,1)} fusion('
            'bf16[2,4096,11008]{2,1,0} %p), kind=kOutput')
    assert devtrace.opcode(name) == "fusion"
    assert devtrace.instruction(name) == "%fusion.349"
    call = ('%x.1 = (bf16[32,2048,128]) custom-call(s32[2048,1] %a, '
            's32[1,2048] %b), custom_call_target="tpu_custom_call"')
    assert devtrace.kernel_kind(call, 12800) == "flash"
    ce = ('%y = (f32[2048,1]) custom-call(bf16[2048,12800] %l, s32[2048,1] '
          '%t), custom_call_target="tpu_custom_call"')
    assert devtrace.kernel_kind(ce, 12800) == "fused_ce"
    assert devtrace.kernel_kind(name, 12800) is None


def test_model_flops_per_token():
    c = load_config("deepseek-llm-7b-1chip")
    assert flops.model_flops(c, [1]) == pytest.approx(2.743e9, rel=1e-3)
    assert flops.attention_flops(c, [2048]) == \
        6 * 2048 ** 2 * 32 * 128 * 2


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
