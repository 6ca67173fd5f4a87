"""Numerics observatory: fused in-graph sentinels (norm parity with the
standalone reduction, bit-exact guarded no-op on non-finite grads), the
online monitor's trip/spike/cooldown behavior, provenance round-trips,
flight-recorder dump retention, the serve engine's failed-request path,
and the 8-device end-to-end: NaN fault -> in-step trip -> provenance
dump -> `python -m repro.obs.replay` reproduces the recorded non-finite
signature bit-exactly."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import get_metrics, get_recorder, get_tracer
from repro.obs import numerics as NU
from repro.obs.anomaly import AnomalyConfig, AnomalyDetector
from repro.optim import adamw
from repro.train.train_step import make_accum_steps

DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
CFG = get_config("llama3.2-3b").reduced()


@pytest.fixture(autouse=True)
def _clean_obs():
    get_metrics().reset()
    get_tracer().clear()
    get_recorder().clear()
    yield
    get_metrics().reset()
    get_tracer().clear()
    get_recorder().clear()


def _tiny_tree(seed=0, nan_at=None):
    rng = np.random.RandomState(seed)
    tree = {"embed": {"w": jnp.asarray(rng.randn(4, 8), jnp.float32)},
            "blocks": {"a": jnp.asarray(rng.randn(3, 5), jnp.float32),
                       "b": jnp.asarray(rng.randn(7), jnp.float32)},
            "final_norm": {"g": jnp.asarray(rng.randn(8), jnp.float32)}}
    if nan_at is not None:
        grp, leaf = nan_at
        arr = np.asarray(tree[grp][leaf]).copy()
        arr.flat[0] = np.nan
        tree[grp][leaf] = jnp.asarray(arr)
    return tree


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# fused sentinels
# ---------------------------------------------------------------------------

def test_gnorm_passthrough_parity():
    """apply_updates with a caller-supplied gnorm (the fused sentinel
    path) must be bit-identical to the standalone-reduction path — the
    one-host-fetch refactor may not change a single bit."""
    params = _tiny_tree(0)
    grads = _tiny_tree(1)
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(lr=1e-3, total_steps=10)
    p1, s1, m1 = adamw.apply_updates(params, grads, state, cfg)
    gn = adamw.global_norm(grads)
    p2, s2, m2 = adamw.apply_updates(params, grads, state, cfg, gnorm=gn)
    assert _tree_equal(p1, p2) and _tree_equal(s1, s2)
    assert float(m1["grad_norm"]) == float(m2["grad_norm"])
    assert float(m1["grad_norm"]) == float(gn)


def test_sentinel_summary_counts_and_groups():
    grads = _tiny_tree(2, nan_at=("blocks", "a"))
    sent = jax.device_get(NU.sentinel_summary(grads))
    assert int(sent["grad_nonfinite"]) == 1
    assert set(k for k in sent if k.startswith("gnorm/")) \
        == {"gnorm/embed", "gnorm/blocks", "gnorm/final_norm"}
    clean = _tiny_tree(2)
    ref = float(np.asarray(adamw.global_norm(clean["embed"])))
    assert float(sent["gnorm/embed"]) == ref


def test_guard_bit_exact():
    """guard=True with finite grads == guard=False bit-exactly (the
    where-select picks identical values); with non-finite grads params
    AND opt state (including the int32 step counter) stay bit-exactly
    untouched and applied==0."""
    from repro.parallel.sharding import single_device_runtime
    rt = single_device_runtime(remat="none")
    opt_cfg = adamw.AdamWConfig(lr=1e-3, total_steps=10)
    _, apply_g = make_accum_steps(CFG, rt, opt_cfg, guard=True)
    _, apply_u = make_accum_steps(CFG, rt, opt_cfg, guard=False)
    params = _tiny_tree(0)
    state = adamw.init_state(params)

    clean = _tiny_tree(1)
    pg, sg, omg = jax.jit(apply_g)(params, state, clean)
    pu, su, omu = jax.jit(apply_u)(params, state, clean)
    assert int(omg["applied"]) == 1 and int(omu["applied"]) == 1
    assert _tree_equal(pg, pu) and _tree_equal(sg, su)

    poisoned = _tiny_tree(1, nan_at=("embed", "w"))
    pn, sn, omn = jax.jit(apply_g)(params, state, poisoned)
    assert int(omn["applied"]) == 0
    assert int(omn["grad_nonfinite"]) == 1
    assert _tree_equal(pn, params)
    assert _tree_equal(sn, state)
    assert int(sn["step"]) == int(state["step"])


# ---------------------------------------------------------------------------
# online monitor
# ---------------------------------------------------------------------------

def test_monitor_clean_stays_silent():
    mon = NU.NumericsMonitor()
    rng = np.random.RandomState(0)
    for t in range(50):
        loss = 2.0 + 0.01 * rng.randn()
        assert mon.observe_wave(t, 0, loss) == []
        mon.observe_step(t, loss, {"grad_norm": 0.5 + 0.01 * rng.randn(),
                                   "grad_nonfinite": 0})
    assert mon.findings == [] and mon.trips == 0


def test_monitor_nonfinite_trips_immediately():
    mon = NU.NumericsMonitor()
    f = mon.observe_wave(0, 3, float("nan"))
    assert f and f[0]["reason"] == "nonfinite_loss" and f[0]["wave"] == 3
    assert f[0]["severity"] == NU.NONFINITE_SEVERITY
    assert f[0]["value"] is None          # NaN -> None for JSON transport
    g = mon.observe_step(0, 2.0, {"grad_norm": 0.5, "grad_nonfinite": 17})
    assert any(x["reason"] == "nonfinite_grads" and x["value"] == 17
               for x in g)
    assert mon.trips == 2


def test_monitor_spike_and_cooldown():
    mon = NU.NumericsMonitor(NU.MonitorConfig(warmup=5, z_thresh=6.0,
                                              cooldown=8))
    for t in range(10):
        mon.observe_step(t, 2.0, {"grad_norm": 0.5, "grad_nonfinite": 0})
    f = mon.observe_step(10, 50.0, {"grad_norm": 0.5, "grad_nonfinite": 0})
    assert any(x["reason"] == "loss_spike" for x in f)
    # within cooldown: silent even though still spiking
    f2 = mon.observe_step(11, 50.0, {"grad_norm": 0.5, "grad_nonfinite": 0})
    assert not any(x["reason"] == "loss_spike" for x in f2)


def test_anomaly_numerics_channel_cooldown():
    det = AnomalyDetector(hdp=4, cfg=AnomalyConfig(numerics_cooldown=4))
    rec = {"step": 10, "findings": [
        {"reason": "nonfinite_loss", "step": 10, "value": None,
         "severity": NU.NONFINITE_SEVERITY, "detail": "wave 0 loss=nan"}]}
    advs = det.ingest_numerics(7, rec)
    assert len(advs) == 1
    a = advs[0]
    assert a.kind == "numerics" and a.worker == 7
    assert a.severity == NU.NONFINITE_SEVERITY
    # same worker, within cooldown -> suppressed; other worker -> passes
    assert det.ingest_numerics(7, {"step": 12, "findings":
                                   rec["findings"]}) == []
    assert len(det.ingest_numerics(8, rec)) == 1
    # grad_nonfinite summary without findings synthesizes one
    advs2 = det.ingest_numerics(9, {"step": 3, "grad_nonfinite": 42,
                                    "findings": []})
    assert len(advs2) == 1 and advs2[0].value == 42.0
    assert det.advisory_counts["numerics"] == 3


# ---------------------------------------------------------------------------
# provenance: fingerprints + manifest round-trips
# ---------------------------------------------------------------------------

def _plan(step=0, seed=0):
    ds = SyntheticDataset(DIST, CFG.vocab_size, tokens_per_step=4096,
                          context=1024, seed=seed)
    sched = GlobalScheduler(ds, CFG, capacity=256, hdp=4, use_offload=False)
    return sched.plan_step(step)


def test_plan_fingerprint_deterministic_and_sensitive():
    a, b = _plan(0), _plan(0)
    assert NU.plan_fingerprint(a) == NU.plan_fingerprint(b)
    assert NU.plan_fingerprint(a) != NU.plan_fingerprint(_plan(1))
    assert NU.plan_fingerprint(a) != NU.plan_fingerprint(_plan(0, seed=1))


def test_manifest_round_trips():
    assert NU.model_from_dict(NU.model_to_dict(CFG)) == CFG
    moe_cfg = get_config("qwen3-moe-30b-a3b").reduced()
    assert NU.model_from_dict(NU.model_to_dict(moe_cfg)) == moe_cfg
    from repro.core.planner import PlanSpec
    spec = PlanSpec.for_config(CFG, capacity=256, hdp=4,
                               strategy="balance", mode="dp",
                               use_offload=False)
    spec2 = NU.spec_from_dict(spec_d := NU.spec_to_dict(spec))
    assert NU.spec_to_dict(spec2) == spec_d
    ds = SyntheticDataset(DIST, CFG.vocab_size, tokens_per_step=4096,
                          context=1024, seed=3)
    ds2 = NU.dataset_from_dict(NU.dataset_to_dict(ds))
    assert ds2.step_lengths(5) == ds.step_lengths(5)
    np.testing.assert_array_equal(np.asarray(ds2.tokens(2, 0, 0, 64)),
                                  np.asarray(ds.tokens(2, 0, 0, 64)))


def test_nonfinite_signature():
    prov = {"sentinels": {"grad_nonfinite": 9}, "applied": 0,
            "wave_losses": [1.0, float("nan"), 2.0, float("inf")]}
    sig = NU.nonfinite_signature(prov)
    assert sig == {"grad_nonfinite": 9, "applied": 0,
                   "nonfinite_waves": [1, 3]}


# ---------------------------------------------------------------------------
# flight-recorder retention
# ---------------------------------------------------------------------------

def test_dump_retention_rotates_oldest_first(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_OBS_MAX_DUMPS", "3")
    rec = get_recorder()
    paths = []
    for i in range(5):
        rec.record("ev", i=i)
        paths.append(rec.dump(f"r{i}"))
    left = sorted(p.name for p in tmp_path.glob("flightrec_*.json"))
    assert len(left) == 3, left
    # the three newest survive, the two oldest rotated out
    for p in paths[-3:]:
        assert os.path.exists(p), p
    for p in paths[:2]:
        assert not os.path.exists(p), p


def test_dump_retention_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_OBS_MAX_DUMPS", "0")     # <=0: keep all
    rec = get_recorder()
    for i in range(5):
        rec.dump(f"k{i}")
    assert len(list(tmp_path.glob("flightrec_*.json"))) == 5


def test_dump_carries_meta(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    rec = get_recorder()
    rec.set_meta("run_manifest", {"seed": 7})
    path = rec.dump("meta_check")
    doc = json.load(open(path))
    assert doc["meta"]["run_manifest"] == {"seed": 7}


# ---------------------------------------------------------------------------
# serve engine: non-finite logits fail the request, not the engine
# ---------------------------------------------------------------------------

def test_serve_nonfinite_logits_fail_request(rt1):
    from repro.serve import ServeConfig, ServeEngine
    from repro.models.transformer import init_params
    params = init_params(jax.random.PRNGKey(0), CFG, rt1)
    scfg = ServeConfig(max_slots=2, max_context=64, prefill_capacity=64)
    eng = ServeEngine(params, CFG, rt1, scfg)
    rng = np.random.RandomState(0)

    # healthy request prefills fine, then params go NaN mid-decode
    rid = eng.submit(rng.randint(0, CFG.vocab_size, 9), 5)
    eng._admit()
    assert eng.n_live == 1
    good = params
    eng.params = jax.tree.map(lambda p: jnp.full_like(p, jnp.nan), params)
    finished = eng._decode_wave()
    assert [r.rid for r in finished] == [rid]
    req = eng.pool.get(rid)
    assert req.error == "nonfinite_logits"
    assert req.telemetry()["error"] == "nonfinite_logits"
    assert eng.n_live == 0                    # slot freed
    assert get_metrics().counter("serve.numerics_failed").value == 1
    assert any(e["kind"] == "serve_numerics" and e["where"] == "decode"
               for e in get_recorder().events())

    # prefill-side failure: NaN params poison the first token's logits
    rid2 = eng.submit(rng.randint(0, CFG.vocab_size, 7), 4)
    eng._admit()
    req2 = eng.pool.get(rid2)
    assert req2.error == "nonfinite_logits" and req2.generated == []
    assert any(e["kind"] == "serve_numerics" and e["where"] == "prefill"
               for e in get_recorder().events())

    # the engine itself survives: healthy params serve the next request
    eng.params = good
    rid3 = eng.submit(rng.randint(0, CFG.vocab_size, 5), 3)
    done = eng.drain(max_steps=50)
    assert [r.rid for r in done] == [rid3]
    assert eng.pool.get(rid3).error is None
    assert len(eng.pool.get(rid3).generated) == 3


# ---------------------------------------------------------------------------
# 8-device end-to-end: fault -> trip -> dump -> bit-exact replay
# ---------------------------------------------------------------------------

E2E_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import get_recorder
from repro.obs.numerics import nonfinite_signature
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig

ckpt_dir = sys.argv[1]
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
             TrainerConfig(capacity=256, attn_impl="ref", calibrate=False,
                           ckpt_dir=ckpt_dir, ckpt_every=1,
                           nan_fault={"step": 2, "wave": 1}))
trip_step = None
for i in range(4):
    tr.train_step()
    if tr.last_numerics["findings"] and trip_step is None:
        trip_step = i
d = os.environ["REPRO_OBS_DIR"]
dumps = sorted(f for f in os.listdir(d) if f.startswith("flightrec_"))
doc = json.load(open(os.path.join(d, dumps[-1])))
provs = [e for e in doc["events"] if e.get("kind") == "step_provenance"]
fault = [p for p in provs if p["applied"] == 0][-1]
print("E2E " + json.dumps({
    "dump": os.path.join(d, dumps[-1]),
    "trip_step": trip_step,
    "applied_seq": [p["applied"] for p in provs[-4:]],
    "losses": [h["loss"] for h in tr.history],
    "fault_step": fault["step"],
    "fault_ckpt": fault["ckpt_step"],
    "signature": nonfinite_signature(fault)}))
"""


def test_numerics_e2e_eight_device_replay(tmp_path):
    """NaN fault on an 8-device trainer: the monitor must trip IN the
    faulted step, the guarded apply must skip, a provenance-bearing dump
    must land, and the replay CLI must reproduce the recorded non-finite
    signature (and wave losses) bit-exactly from the checkpoint."""
    obs_dir = tmp_path / "obs"
    ckpt_dir = tmp_path / "ckpt"
    obs_dir.mkdir()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", E2E_SCRIPT, str(ckpt_dir)],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "PYTHONPATH": "src",
             "REPRO_OBS_DIR": str(obs_dir)}, cwd=repo)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("E2E ")]
    assert line, r.stdout
    out = json.loads(line[0][len("E2E "):])
    assert out["trip_step"] == 2          # tripped in the faulted step
    assert out["fault_step"] == 2
    assert out["signature"]["applied"] == 0
    assert out["signature"]["grad_nonfinite"] > 0
    assert out["signature"]["nonfinite_waves"] == [1]
    assert not math.isfinite(out["losses"][2])
    assert math.isfinite(out["losses"][3])     # guarded continuation

    # replay in a fresh process (fresh obs dir: the replayed NaN trips
    # the replay trainer's own monitor, which is expected to dump too)
    replay_obs = tmp_path / "replay_obs"
    replay_obs.mkdir()
    rr = subprocess.run(
        [sys.executable, "-m", "repro.obs.replay", out["dump"], "--json"],
        capture_output=True, text=True, timeout=1200,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": "src", "REPRO_OBS_DIR": str(replay_obs)},
        cwd=repo)
    assert rr.returncode == 0, (rr.stdout[-2000:], rr.stderr[-3000:])
    jline = [l for l in rr.stdout.splitlines()
             if l.startswith("REPLAY_JSON ")]
    assert jline, rr.stdout
    rep = json.loads(jline[0][len("REPLAY_JSON "):])
    assert rep["ok"] and rep["plan_hash_ok"]
    assert rep["signature_ok"] and rep["losses_exact"]
    tgt = rep["target_step"]
    assert tgt["replayed_signature"] == out["signature"]
    assert tgt["recorded_signature"] == out["signature"]
    assert rep["restored_ckpt"] == out["fault_ckpt"] == 2
