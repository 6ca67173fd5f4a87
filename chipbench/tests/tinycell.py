"""A checkout with one tiny cell, for the benchmark's CPU tests."""
import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent

# limits for the tiny cell on the CPU, set as the cells' are: program
# readings on CPU seeds (loss 1e-4, gradient norm 1.5e-3, grad 2e-3,
# update 1.2e-3) and the float8 control's (loss 9e-4, gradient norm
# 8.8e-3, grad 1.4e-2, update 6e-3)
TINY_LIMITS = {"loss_gap": 4e-4, "gnorm_gap": 4e-3, "grad_gap": 7e-3,
               "update_gap": 3.5e-3}


def write_tiny_root(root: Path) -> str:
    """A checkout holding BENCHMARK.json and chipbench data for one tiny
    one-device cell named ``tiny.w``, with the real per-layer readers;
    returns the cell name."""
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    data = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (data / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", data / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    c = json.loads((BENCH / "configs" / real["configs"][0]["file"]
                    .rsplit("/", 1)[1]).read_text())
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=512)
    (data / "configs" / "tiny.json").write_text(json.dumps(c))
    m = json.loads((BENCH / "traffic" / "github2k.json").read_text())
    m.update(tokens_per_step=1024, capacity=256, max_len=256)
    (data / "traffic" / "tiny.json").write_text(json.dumps(m))
    (data / "limits" / "tiny.w.json").write_text(json.dumps(
        {k: {"limit": v} for k, v in TINY_LIMITS.items()}))
    bench = dict(real, configs=[dict(real["configs"][0], name="tiny",
                                     file="chipbench/configs/tiny.json")],
                 workloads=[{"name": "tiny.w", "config": "tiny",
                             "traffic": "tiny", "chips": 1,
                             "why": "tiny"}],
                 per_layer=[{k: v for k, v in p.items() if k != "workloads"}
                            for p in real["per_layer"]])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.w"


