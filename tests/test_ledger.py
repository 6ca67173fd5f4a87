"""Bytes-ledger correctness: analytic pricing properties, fleet-totals
conservation, and end-to-end predicted == measured exactness on a real
8-device trainer (the trace-time tally audits the analytic cost model
against what the instrumented collectives actually move)."""
import json
import math
import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

from repro import compat
from repro.configs.registry import get_config
from repro.core import offload as OF
from repro.core.planner import PlanSpec, plan as plan_batch
from repro.obs import ledger

CFG = get_config("llama-7b")
SMALL = get_config("llama3.2-3b").reduced()


# ---------------------------------------------------------------------------
# analytic pricing properties
# ---------------------------------------------------------------------------

def test_singleton_groups_move_zero_ring_bytes():
    """The HDP claim the ledger must encode: unsharded sequences pay no
    ring traffic at all, whatever the capacity."""
    for comp in ([1], [1, 1, 1, 1], [1] * 8):
        assert ledger.wave_ring_bytes(CFG, comp, 8192) == 0.0
    assert ledger.ring_edges([1, 1, 1, 1]) == 0


def test_ring_edges_counts_groups_larger_than_one():
    assert ledger.ring_edges([4, 2, 1, 1]) == 6
    assert ledger.ring_edges([8]) == 8
    assert ledger.ring_edges([]) == 0


@settings(max_examples=30, deadline=None)
@given(comp=st.lists(st.integers(1, 8), min_size=1, max_size=8),
       cap=st.sampled_from([1024, 4096, 8192]))
def test_wave_ring_bytes_finite_nonnegative_and_edge_scaled(comp, cap):
    b = ledger.wave_ring_bytes(CFG, comp, cap)
    assert math.isfinite(b) and b >= 0.0
    steps = max(comp) - 1
    if steps <= 0:
        assert b == 0.0
    else:
        # per attention layer: steps x edges x one KV block
        blk = ledger.ring_block_bytes(CFG, cap)
        assert b == pytest.approx(ledger.attn_layer_count(CFG) * steps
                                  * ledger.ring_edges(comp) * blk)


@settings(max_examples=15, deadline=None)
@given(lens=st.lists(st.integers(64, 32768), min_size=4, max_size=64))
def test_plan_pricing_hdp_never_above_static(lens):
    """Conservation over random length mixes: for the SAME batch, the
    balance planner's priced comm never exceeds static CP's (static
    shards every wave at the full fixed composition; balance only shards
    what spills a rank)."""
    spec = PlanSpec.for_config(CFG, capacity=8192, hdp=8,
                               use_offload=False)
    priced = {}
    for strat in ("balance", "static"):
        p = plan_batch(lens, spec.replace(strategy=strat))
        # every wave's composition accounts every rank of the hdp group
        for w in p.waves:
            assert sum(w.composition) == 8
        priced[strat] = ledger.plan_comm_bytes(p, CFG)["total"]
    assert priced["balance"] <= priced["static"]


def test_plan_pricing_bimodal_mix_strictly_cheaper_under_hdp():
    """On the paper's bimodal mix (a few 4x-capacity longs, many shorts)
    the saving must be strict — this is the CI BENCH_comm gate in
    miniature."""
    lens = [4 * 8192] * 3 + [512] * 200
    spec = PlanSpec.for_config(CFG, capacity=8192, hdp=8,
                               use_offload=False)
    hdp_b = ledger.plan_comm_bytes(
        plan_batch(lens, spec.replace(strategy="balance")), CFG)["total"]
    static_b = ledger.plan_comm_bytes(
        plan_batch(lens, spec.replace(strategy="static")), CFG)["total"]
    assert 0.0 <= hdp_b < static_b


def test_offload_prediction_quantization_matches_eq3_bytes():
    """predict_dispatch's offload channel prices the continuous Eq. 3
    ratio; execution moves whole periods.  The gap between the two is
    exactly the ratio -> period rounding, never more than one period's
    bytes."""
    cfg = SMALL
    n = OF.scan_periods(cfg)
    t_glob = 4 * 256
    resid = t_glob * cfg.d_model * ledger.act_itemsize(cfg)
    for r in (0.1, 0.37, 0.5, 0.93, 1.0):
        d2h, h2d = ledger.offload_dispatch_bytes(cfg, r, t_glob)
        assert d2h == h2d == pytest.approx(r * n * resid)
        k = min(OF.offload_periods(cfg, r), n)       # executed periods
        assert abs(d2h - k * resid) <= resid + 1e-6


def test_predicted_hbm_monotone_in_offload_ratio():
    led = ledger.Ledger(SMALL, capacity=256, hdp=4, offload_active=True)
    hbm = [led.predict_hbm(c_mult=4, offload_ratio=r)
           for r in (0.0, 0.5, 1.0)]
    assert hbm[0] > hbm[1] > hbm[2] > 0


# ---------------------------------------------------------------------------
# fleet totals / merge conservation
# ---------------------------------------------------------------------------

def test_merge_record_conserves_totals():
    tot = ledger.new_totals()
    recs = [{"pred": {"ring": 100.0, "pp": 10.0},
             "meas": {"ring": 90.0, "pp": 10.0}, "hbm_pred": 7,
             "hbm_meas": 5.0},
            {"pred": {"ring": 50.0}, "meas": {"ring": 60.0}},
            {"pred": {"ring": 25.0}}]                # no measured side
    for r in recs:
        ledger.merge_record(tot, r)
    s = ledger.totals_summary(tot)
    assert s["n"] == 3
    assert s["pred_total"] == pytest.approx(185.0)
    assert s["meas_total"] == pytest.approx(160.0)
    # per-kind residuals off the summed totals: ring pred=175 meas=150
    assert s["residual"]["ring"] == pytest.approx(25.0 / 175.0)
    assert s["residual"]["pp"] == pytest.approx(0.0)
    assert s["hbm_pred_peak"] == 7 and s["hbm_meas_peak"] == 5.0


def test_ledger_record_dispatch_accumulates_and_bounds_memory():
    led = ledger.Ledger(SMALL, capacity=256, hdp=4, max_records=4)
    for i in range(10):
        led.record_dispatch(step=0, idx=i, kind="wave",
                            composition=(2, 1, 1), c_mult=1,
                            offload_ratio=0.0,
                            measured={"ring": 1.0})
    assert len(led.recent(100)) == 4                 # ring buffer bound
    assert led.summary()["n"] == 10                  # totals cover all
    assert led.summary()["pred_total"] > 0


def test_comm_residual_zero_when_measured_matches():
    led = ledger.Ledger(SMALL, capacity=256, hdp=4)
    pred = led.predict_dispatch((2, 1, 1), c_mult=1, offload_ratio=0.0)
    led.record_dispatch(step=0, idx=0, kind="wave", composition=(2, 1, 1),
                        c_mult=1, offload_ratio=0.0, measured=dict(pred))
    assert led.comm_residual() == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# compat memory-stats shim (both paths)
# ---------------------------------------------------------------------------

def test_device_memory_stats_returns_dict_on_bare_backend():
    # CPU jaxlib exposes no allocator stats -> {} (never raises)
    out = compat.device_memory_stats()
    assert isinstance(out, dict)


def test_device_memory_stats_passes_through_real_stats():
    class FakeDev:
        def memory_stats(self):
            return {"peak_bytes_in_use": 123}

    class BrokenDev:
        def memory_stats(self):
            raise RuntimeError("no allocator")

    assert compat.device_memory_stats(FakeDev()) == \
        {"peak_bytes_in_use": 123}
    assert compat.device_memory_stats(BrokenDev()) == {}


# ---------------------------------------------------------------------------
# end-to-end exactness: 8-device trainer, predicted == measured
# ---------------------------------------------------------------------------

EXACTNESS_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import set_ledger_enabled
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig

set_ledger_enabled(True)
cfg = get_config("llama3.2-3b").reduced()
mesh = compat.make_mesh((8, 1), ("data", "model"))
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
             remat="none", kv_chunk=64)
dist = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
ds = SyntheticDataset(dist, cfg.vocab_size, tokens_per_step=4096,
                      context=1024)
sched = GlobalScheduler(ds, cfg, capacity=256, hdp=8, use_offload=False)
tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=8), sched,
             TrainerConfig(capacity=256, attn_impl="ref"))
for _ in range(2):
    tr.train_step()
s = tr.ledger.summary()
recs = tr.ledger.recent(256)
exact = all(r["pred"]["ring"] == r["meas"]["ring"]
            for r in recs if "meas" in r)
n_meas = sum(1 for r in recs if "meas" in r)
n_ring = sum(1 for r in recs if r["pred"]["ring"] > 0)
print("LEDGER", json.dumps({
    "residual": s["comm_residual"], "exact": exact,
    "n": s["n"], "n_meas": n_meas, "n_ring": n_ring,
    "pred_total": s["pred_total"], "meas_total": s["meas_total"]}))
"""


def test_ledger_exact_on_eight_device_oracle_ring():
    """Every fresh-compiled dispatch's measured ring tally must equal the
    analytic prediction EXACTLY (same shapes, same dtype table — any
    drift is a cost-model bug, not noise), so the fleet residual is 0."""
    r = subprocess.run(
        [sys.executable, "-c", EXACTNESS_SCRIPT],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("LEDGER ")]
    assert line, r.stdout
    out = json.loads(line[0][len("LEDGER "):])
    assert out["exact"], out
    assert out["residual"] == 0.0, out
    assert out["n_meas"] > 0 and out["n_ring"] > 0, out
    assert out["pred_total"] == out["meas_total"] > 0, out
