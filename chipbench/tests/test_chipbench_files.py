"""A cell, a traffic mix and a per-layer metric are files found by name:
adding a mix file and a reader file (and their entries) is all a new
cell or metric takes."""
import json

import jax

import run
from tinycell import BENCH
from test_chipbench_devtrace import recorded_ctx

DUMMY_MIX = {"mix": {"lognorm_mean": 5.0, "lognorm_sigma": 0.5,
                     "tail_frac": 0.0, "tail_alpha": 1.0, "tail_scale": 64},
             "min_len": 16, "max_len": 128, "tokens_per_step": 512,
             "capacity": 128, "strategy": "balance", "pool_steps": 2,
             "pool_seed": 3}
DUMMY_READER = '''
def read(ctx):
    return 100.0 * ctx["trace"].kernel_s("flash") / ctx["trace"].window_s
'''


def _add_files(root):
    data = root / "chipbench"
    (data / "traffic" / "dummy.json").write_text(json.dumps(DUMMY_MIX))
    (data / "metrics" / "dummy_flash_share.py").write_text(DUMMY_READER)
    (data / "limits" / "tiny.dummy.json").write_text(
        (data / "limits" / "tiny.w.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.dummy", "config": "tiny",
                               "traffic": "dummy", "chips": 1,
                               "why": "dummy"})
    bench["per_layer"].append({"name": "dummy_flash_share", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "attention kernels",
                               "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def test_new_traffic_file_runs(tiny_root, capsys):
    _add_files(tiny_root)
    rc = run.main(["--workload", "tiny.dummy", "--seed", "5", "--seconds",
                   "0.2", "--trace", "0"], root=tiny_root,
                  devices=jax.devices()[:1])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "mfu", "setup_s"}


def test_new_metric_file_is_read(tiny_root):
    bench = _add_files(tiny_root)
    ctx = recorded_ctx()
    cell = next(w for w in bench["workloads"] if w["name"] == "tiny.dummy")
    metrics = run.per_layer(bench, cell, ctx, tiny_root)
    assert 0 < metrics["dummy_flash_share"]["value"] < 100
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    one_chip = [m["name"] for m in real["per_layer"]
                if "workloads" not in m]
    assert set(one_chip) <= set(metrics)
