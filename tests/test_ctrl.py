"""Control plane: RPC framing, §6.1 telemetry ingestion parity, 2-worker
bit-parity vs the single-process trainer, and failure injection (kill one
worker → membership shrink → plans re-snap onto the surviving divisor
grid → checkpoint resume with loss parity).

The multi-process scenarios run inside a subprocess driver (like
test_distributed) so the workers' forced-device-count environments never
leak into the smoke tests; worker subprocesses get their own env from
launch/cluster.py."""
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.planner import PlanSpec
from repro.sched.calibrate import OnlineCalibrator

CFG = get_config("llama3.2-3b").reduced()


# ---------------------------------------------------------------------------
# RPC framing
# ---------------------------------------------------------------------------

def test_rpc_roundtrip_and_eof():
    from repro.ctrl.rpc import Listener, connect
    lst = Listener()
    got = {}

    def server():
        chan = lst.accept(timeout=10.0)
        got["first"] = chan.recv()
        chan.send({"type": "echo", "arr": got["first"]["arr"] * 2})
        got["second"] = chan.recv()
        chan.close()

    th = threading.Thread(target=server)
    th.start()
    cli = connect(lst.address)
    arr = np.arange(7, dtype=np.float32)
    cli.send({"type": "hello", "arr": arr})
    echo = cli.recv()
    np.testing.assert_array_equal(echo["arr"], arr * 2)
    cli.send({"type": "bye"})
    th.join(timeout=10.0)
    assert got["second"]["type"] == "bye"
    with pytest.raises(EOFError):       # server closed: reads EOF, loudly
        cli.recv()
    cli.close()
    lst.close()


# ---------------------------------------------------------------------------
# telemetry ingestion (paper §6.1) == the wave_time_fn hook path
# ---------------------------------------------------------------------------

def test_ingest_matches_wave_time_hook():
    """Per-worker partial reports assembled by `ingest` must produce
    exactly the calibrator state the deprecated single-process
    `wave_time_fn` hook produces from the same fake per-rank clock."""
    spec = PlanSpec.for_config(CFG, capacity=512, hdp=4, use_offload=False)
    hook = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    ctrl = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    rng = np.random.default_rng(0)
    speed = np.array([1.0, 1.0, 1 / 3, 1.0])     # rank 2 runs 3x slow
    for _ in range(8):
        costs = rng.uniform(0.5, 2.0, size=4)
        times = costs / speed                    # identical fake clocks
        hook.observe(costs, rank_seconds=times)  # trainer hook path
        ctrl.ingest(costs, [([0, 1], times[:2]),  # worker 0 owns {0,1}
                            ([2, 3], times[2:])])  # worker 1 owns {2,3}
    np.testing.assert_array_equal(hook.rank_speed(), ctrl.rank_speed())
    assert hook._scale == ctrl._scale
    assert hook.n_observed == ctrl.n_observed
    slow = ctrl.rank_speed()
    assert slow[2] < np.delete(slow, 2).min()


def test_ingest_skips_fresh_compiles_and_partial_coverage():
    spec = PlanSpec.for_config(CFG, capacity=512, hdp=4, use_offload=False)
    cal = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    costs = np.ones(4)
    cal.ingest(costs, [([0, 1], [1.0, 1.0])], fresh=True)
    assert cal.n_observed == 0                   # compile-polluted: skip
    cal.ingest(costs, [([0, 1], [2.0, 2.0])])    # ranks 2,3 never report
    assert cal.n_observed == 1                   # (dead worker): partial
    s = cal.rank_speed()                         # coverage still observes
    assert s[0] == s[1]


def test_ingest_wall_attributed_degrades_to_bottleneck_blame():
    """exact=False (a worker attributed one wall clock to all its ranks):
    the observation must take the wall channel — bottleneck-blamed — and
    NOT mark lightly-loaded ranks slow by dividing their small cost by
    the shared wall."""
    spec = PlanSpec.for_config(CFG, capacity=512, hdp=4, use_offload=False)
    wall = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    ctrl = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    costs = np.array([2.0, 0.5, 0.5, 0.5])     # imbalanced: rank 0 heavy
    for _ in range(6):
        wall.observe(costs, seconds=2.2)        # single-process wall path
        ctrl.ingest(costs, [([0, 1], [2.2, 2.2]), ([2, 3], [2.2, 2.2])],
                    exact=False)
    np.testing.assert_array_equal(wall.rank_speed(), ctrl.rank_speed())
    s = ctrl.rank_speed()
    assert s[1] == s[2] == s[3]                 # idle-ish ranks untouched,
    assert s[1] >= s[0]                         # never dragged below the
                                                # blamed bottleneck


def test_calibrator_state_roundtrip_and_rank_map():
    spec = PlanSpec.for_config(CFG, capacity=512, hdp=4, use_offload=False)
    cal = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    speed = np.array([1.0, 1.0, 1 / 3, 1.0])
    rng = np.random.default_rng(1)
    for _ in range(6):
        costs = rng.uniform(0.5, 2.0, size=4)
        cal.observe(costs, rank_seconds=costs / speed)
    state = cal.state_dict()
    # identity restore
    cal2 = OnlineCalibrator(spec.coeffs, 4, CFG.num_layers)
    cal2.load_state(state)
    np.testing.assert_array_equal(cal.rank_speed(), cal2.rank_speed())
    # elastic shrink: survivors are old ranks [2, 3] — the slow rank's
    # learned speed follows it to new rank 0 (warm restart)
    cal3 = OnlineCalibrator(spec.coeffs, 2, CFG.num_layers)
    cal3.load_state(state, rank_map=[2, 3])
    assert cal3._speed[0] == cal._speed[2]
    assert cal3._speed[1] == cal._speed[3]
    # geometry mismatch without a map: no-op, not corruption
    cal4 = OnlineCalibrator(spec.coeffs, 2, CFG.num_layers)
    cal4.load_state(state)
    np.testing.assert_array_equal(cal4._speed, np.ones(2))
    # double-shrink guard: a rank_map over a 6-world must not index a
    # 4-world snapshot (newest checkpoint can predate the first shrink)
    cal5 = OnlineCalibrator(spec.coeffs, 2, CFG.num_layers)
    cal5.load_state(state, rank_map=[0, 1], src_world=6)
    np.testing.assert_array_equal(cal5._speed, np.ones(2))
    # ...but the matching world applies normally
    cal6 = OnlineCalibrator(spec.coeffs, 2, CFG.num_layers)
    cal6.load_state(state, rank_map=[2, 3], src_world=4)
    assert cal6._speed[0] == cal._speed[2]


# ---------------------------------------------------------------------------
# 2-worker cluster == single-process trainer, bit for bit
# ---------------------------------------------------------------------------

PARITY_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.core.planner import PlanSpec
from repro.ctrl.controller import Controller, ControllerConfig
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.launch.cluster import LocalCluster
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig

cfg = get_config("llama3.2-3b").reduced()
DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
STEPS, HDP, CAP = 3, 4, 256
RT_KW = {"remat": "none", "kv_chunk": 64}

def make_ds():
    return SyntheticDataset(DIST, cfg.vocab_size, tokens_per_step=2048,
                            context=1024)

# controller + 2 worker processes; buffers materialized controller-side
# and shipped with the plan; calibration off so plans depend only on the
# data (the bit-parity setting, same as the async/sync parity test)
spec = PlanSpec.for_config(cfg, capacity=CAP, hdp=HDP, use_offload=False)
ctl = Controller(make_ds(), cfg, spec, ControllerConfig(
    num_workers=2, steps=STEPS, lookahead=2, calibrate=False,
    ship_buffers=True, runtime_kw=RT_KW, opt_kw={"lr": 1e-3}))
cluster = LocalCluster(ctl)
cluster.start()
try:
    hist = cluster.run()
finally:
    cluster.shutdown()
assert len(hist) == STEPS, hist
assert all(r["workers"] == 2 for r in hist), hist

# single-process reference on the same data/spec/geometry
mesh = compat.make_mesh((HDP, 1), ("data", "model"))
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model", **RT_KW)
sched = GlobalScheduler(make_ds(), cfg, capacity=CAP, hdp=HDP,
                        use_offload=False, lookahead=2)
tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=STEPS), sched,
             TrainerConfig(capacity=CAP, calibrate=False))
ref = [tr.train_step()["loss"] for _ in range(STEPS)]
got = [r["loss"] for r in hist]
assert got == ref, (got, ref)
print("CTRL_PARITY_OK")
"""


def test_controller_2worker_bit_parity():
    """Acceptance: a 2-worker controller-driven run matches the
    single-process trainer's loss trajectory bit-for-bit on the same
    data/plan."""
    r = subprocess.run([sys.executable, "-c", PARITY_DRIVER],
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CTRL_PARITY_OK" in r.stdout


# ---------------------------------------------------------------------------
# end-to-end straggler detection through worker telemetry
# ---------------------------------------------------------------------------

STRAGGLER_DRIVER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from repro.configs.registry import get_config
from repro.core.planner import PlanSpec, plan as plan_batch
from repro.ctrl.controller import Controller, ControllerConfig
from repro.data.distribution import LengthDistribution
from repro.data.loader import SyntheticDataset
from repro.launch.cluster import LocalCluster

cfg = get_config("llama3.2-3b").reduced()
DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
ds = SyntheticDataset(DIST, cfg.vocab_size, tokens_per_step=2048,
                      context=1024)
SLOW = 2
spec = PlanSpec.for_config(cfg, capacity=256, hdp=4, use_offload=False)
ctl = Controller(ds, cfg, spec, ControllerConfig(
    num_workers=2, steps=3, calibrate=True,
    slow_ranks={SLOW: 3.0},        # fault-injection drill: rank 2 is 3x
    runtime_kw={"remat": "none", "kv_chunk": 64}, opt_kw={"lr": 1e-3}))
cluster = LocalCluster(ctl)
cluster.start()
try:
    cluster.run()
finally:
    cluster.shutdown()
# worker 1 owns ranks {2,3}: its telemetry must localize the slow rank
speed = ctl.calib.rank_speed()
others = np.delete(speed, SLOW)
assert speed[SLOW] < others.min(), speed
# and planning with the learned speeds gives the slow rank less work
p = plan_batch(ds.step_lengths(99),
               ctl.spec.replace(rank_speed=speed, snap_widths=True))
work = np.zeros(4)
for w in p.waves:
    work += np.asarray(w.costs)
assert work[SLOW] < work.mean(), work
print("CTRL_STRAGGLER_OK")
"""


def test_cluster_telemetry_localizes_straggler():
    """End-to-end §6.1: a 3x-slow rank injected on ONE worker's fake
    clock is localized by the controller's calibrator from the partial
    per-rank reports, and future plans de-weight it."""
    r = subprocess.run([sys.executable, "-c", STRAGGLER_DRIVER],
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CTRL_STRAGGLER_OK" in r.stdout


# ---------------------------------------------------------------------------
# failure injection: kill → shrink → re-plan on divisor grid → resume
# ---------------------------------------------------------------------------

ELASTIC_DRIVER = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from repro import compat
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.registry import get_config
from repro.core.planner import PlanSpec
from repro.ctrl.controller import Controller, ControllerConfig
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.launch.cluster import LocalCluster
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig

cfg = get_config("llama3.2-3b").reduced()
DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
STEPS, HDP, CAP = 6, 4, 256
RT_KW = {"remat": "none", "kv_chunk": 64}
tdir = tempfile.mkdtemp()

def make_ds():
    return SyntheticDataset(DIST, cfg.vocab_size, tokens_per_step=2048,
                            context=1024)

spec = PlanSpec.for_config(cfg, capacity=CAP, hdp=HDP, use_offload=False)
ctl = Controller(make_ds(), cfg, spec, ControllerConfig(
    num_workers=2, steps=STEPS, lookahead=2, calibrate=False,
    ckpt_dir=tdir, ckpt_every=2, runtime_kw=RT_KW, opt_kw={"lr": 1e-3}))
cluster = LocalCluster(ctl)
cluster.start()
killed = []

def on_step(c, rec):
    # after 3 completed steps (checkpoint at step 2 exists): hard-kill
    # one worker -> EOF -> MembershipChange on the next dispatch
    if rec["step"] == 3 and not killed:
        cluster.kill_worker(1)
        killed.append(True)

try:
    hist = cluster.run(on_step=on_step)
finally:
    cluster.shutdown()

pre = [r for r in hist if r["hdp"] == HDP]
post = [r for r in hist if r["hdp"] != HDP]
assert killed and pre and post, (killed, hist)
new_hdp = post[0]["hdp"]
assert new_hdp == 2 and all(r["workers"] == 1 for r in post), post
# ACCEPTANCE: every post-resume plan width divides the surviving HDP size
for r in post:
    for comp in r["compositions"]:
        for g in comp:
            assert new_hdp % g == 0, (g, new_hdp, r)
assert post[-1]["step"] == STEPS, post

# loss parity after restore: a single-process run at the surviving HDP
# size, restored from the SAME checkpoint the cluster resumed from, must
# reproduce the post-resume trajectory bit-for-bit
resume = post[0]["step"] - 1
mesh = compat.make_mesh((new_hdp, 1), ("data", "model"))
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model", **RT_KW)
sched = GlobalScheduler(make_ds(), cfg, capacity=CAP, hdp=new_hdp,
                        use_offload=False, lookahead=2)
tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=STEPS), sched,
             TrainerConfig(capacity=CAP, calibrate=False))
if resume:          # resume==0 only if the kill raced the very first save
    mgr = CheckpointManager(tdir)
    tr.params, tr.opt_state, dstate = mgr.restore(resume, tr.params,
                                                  tr.opt_state)
    tr.step = int(dstate["step"])
    assert tr.step == resume, (tr.step, resume)
ref = [tr.train_step()["loss"] for _ in range(STEPS - resume)]
got = [r["loss"] for r in post]
assert got == ref, (got, ref)
print("ELASTIC_OK")
"""


def test_elastic_kill_shrink_resume():
    """Acceptance: killing a worker mid-run triggers membership shrink,
    re-planning with widths on the surviving divisor grid, and a
    checkpoint resume whose trajectory matches a single-process restore
    bit-for-bit."""
    r = subprocess.run([sys.executable, "-c", ELASTIC_DRIVER],
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ELASTIC_OK" in r.stdout


# ---------------------------------------------------------------------------
# serve mode: the control plane as a request router
# ---------------------------------------------------------------------------

SERVE_DRIVER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import threading
import numpy as np
from repro.configs.registry import get_config
from repro.core.planner import PlanSpec
from repro.ctrl.controller import Controller, ControllerConfig
from repro.launch.cluster import LocalCluster
from repro import compat
from repro.models.transformer import init_params
from repro.parallel.sharding import Runtime
from repro.serve import ServeConfig, ServeEngine
from repro.serve.router import ServeClient

cfg = get_config("llama3.2-3b").reduced()
RT_KW = {"remat": "none", "kv_chunk": 16}
SERVE = {"max_slots": 2, "max_context": 64, "prefill_capacity": 64}
REQS = [(9, 4), (5, 3), (12, 5)]

spec = PlanSpec.for_config(cfg, capacity=64, hdp=1, use_offload=False)
ctl = Controller(None, cfg, spec, ControllerConfig(
    num_workers=1, serve=SERVE, runtime_kw=RT_KW))
cluster = LocalCluster(ctl, devices_per_worker=1)
addr = cluster.start()
ctl.wait_for_workers()
th = threading.Thread(target=ctl.run_serve, daemon=True)
th.start()

cli = ServeClient(addr)
rng = np.random.RandomState(0)
prompts = [rng.randint(0, cfg.vocab_size, n) for n, _ in REQS]
tags = [cli.submit(p, m) for p, (_, m) in zip(prompts, REQS)]
outs = [cli.result(t, timeout=600) for t in tags]
ctl.stop_serving()
th.join(timeout=60)
cli.close()
cluster.shutdown()

# the routed results match a local engine on the same params (same seed)
mesh = compat.make_mesh((1, 1), ("data", "model"))
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model", **RT_KW)
params = init_params(__import__("jax").random.PRNGKey(0), cfg, rt)
eng = ServeEngine(params, cfg, rt, ServeConfig(**SERVE))
rids = [eng.submit(p, m) for p, (_, m) in zip(prompts, REQS)]
eng.drain(max_steps=500)
for rid, out, (_, m) in zip(rids, outs, REQS):
    ref = eng.pool.get(rid).generated
    assert out["tokens"] == ref, (out["tokens"], ref)
    assert len(out["tokens"]) == m
    assert out["telemetry"]["n_tokens"] == m
    assert out["telemetry"]["worker"] == 0
    assert out["telemetry"]["e2e_s"] > 0
assert len(ctl.request_log) == len(REQS), ctl.request_log
print("CTRL_SERVE_OK")
"""


def test_ctrl_serve_routes_requests():
    """Acceptance: the controller/worker runtime serves traffic over the
    same RPC channel it trains with — a ServeClient's routed results are
    token-identical to a local ServeEngine on the same params, and the
    controller logs per-request telemetry."""
    r = subprocess.run([sys.executable, "-c", SERVE_DRIVER],
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CTRL_SERVE_OK" in r.stdout
