"""Worker agent: wraps the SPMD `Trainer` loop under controller command.

The agent owns a contiguous slice of the global HDP axis.  It builds the
mesh/Runtime/Trainer from the controller's config message, then drives
`Trainer.train_step` with plans that arrive over the channel (the
`RemotePlanClient` below is a `GlobalScheduler`-shaped facade whose
`get_step` blocks on the wire instead of a planner thread).  After each
step it reports:

* the step record (loss, grad norm) — the controller's history;
* its warm compile keys — seed the controller's template registry, the
  NCCL-group-cache analogue;
* **per-rank telemetry** (§6.1): for every dispatched wave/round, the wall
  times of exactly the ranks it owns.  The controller assembles the
  partial reports from all workers into full per-rank vectors
  (`OnlineCalibrator.ingest`) — true worker→controller telemetry instead
  of the single-process trainer's bottleneck attribution.

A dedicated thread heartbeats every ``heartbeat_interval`` so the elastic
supervisor can distinguish "slow" from "gone".  On RECONFIG (membership
shrank) the agent tears the trainer down, rebuilds mesh+Runtime at the
surviving HDP size, restores params through the re-sharding checkpoint
path, and resumes; on SHUTDOWN the checkpoint owner writes a final
checkpoint and says goodbye.

Runnable: ``python -m repro.ctrl.worker --addr HOST:PORT`` (the launcher
sets XLA flags in the child environment before this module imports jax).
"""
from __future__ import annotations

import argparse
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from repro import compat
from repro.ctrl.rpc import Channel, connect
from repro.launch.mesh import make_pipeline_mesh
from repro.obs import (configure as obs_configure, get_recorder,
                       get_tracer, monotime)
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig


def make_telemetry_record(ranks, measured, fresh: bool,
                          step: Optional[int] = None,
                          wall_s: Optional[float] = None) -> Dict:
    """One dispatched wave (or pipelined round) as a wire record.  A
    scalar measurement (real wall clock) is this process's local time —
    attributed to every owned rank, which is exactly what a per-host
    agent can observe; a vector (fault-injection clock) is sliced to the
    owned ranks.  ``wall_s`` is the TRUE host wall of the dispatch —
    identical to a scalar ``measured``, but still real when ``measured``
    is a modeled fault-clock vector (the anomaly detector subtracts it
    from the record-to-record cadence to isolate dispatch idle).  Every
    record is double-stamped — ``t_mono`` for intra-process ordering,
    ``t_wall`` for cross-worker trace alignment (monotonic clocks share
    no epoch across processes)."""
    exact = np.ndim(measured) > 0
    if exact:
        times = np.asarray(measured, float)[list(ranks)]
    else:
        times = np.full(len(ranks), float(measured))
    rec = {"ranks": list(ranks),
           "times": [float(t) for t in times],
           "exact": exact,        # per-rank clock vs the wall attributed
                                  # to every owned rank
           "fresh": bool(fresh),
           "t_mono": monotime(), "t_wall": time.time()}
    if wall_s is not None:
        rec["wall_s"] = float(wall_s)
    if step is not None:
        rec["step"] = int(step)
    return rec


class Reconfigure(Exception):
    def __init__(self, msg: dict):
        self.msg = msg
        super().__init__("membership reconfig")


class Shutdown(Exception):
    pass


class RemotePlanClient:
    """The worker-side face of the scheduler: plans (and optionally
    pre-built buffers and the controller's state snapshot) arrive over
    the channel.  Shaped like `GlobalScheduler` so the Trainer is
    unchanged; feedback methods are no-ops — calibration is the
    controller's job, fed by the agent's telemetry stream."""

    def __init__(self, ds, spec, chan: Channel, on_state=None):
        self.ds = ds
        self.spec = spec            # Trainer._align_offload may rewrite
        self.chan = chan
        self.on_state = on_state
        self.rank_speed = None

    @property
    def hdp(self) -> int:
        return self.spec.hdp

    @property
    def capacity(self) -> int:
        return self.spec.capacity

    def get_step(self, step: int):
        while True:
            msg = self.chan.recv()
            mtype = msg.get("type")
            if mtype == "plan":
                if msg["step"] < step:
                    continue        # stale dispatch from before a replay
                assert msg["step"] == step, (msg["step"], step)
                if self.on_state is not None:
                    self.on_state(msg.get("state"))
                return msg["plan"], msg.get("waves")
            if mtype == "reconfig":
                raise Reconfigure(msg)
            if mtype == "shutdown":
                raise Shutdown()

    def plan_step(self, step: int):
        return self.get_step(step)[0]

    def update_rank_speed(self, speed) -> None:
        pass                        # controller-owned

    def update_coeffs(self, coeffs) -> None:
        pass

    def stop(self) -> None:
        pass


class WorkerAgent:
    def __init__(self, address: str, connect_timeout: float = 120.0):
        self.chan = connect(address, timeout=connect_timeout)
        self.ranks: List[int] = []
        self.trainer: Optional[Trainer] = None
        self._telemetry: List[Dict] = []
        self._stream_pending: List[Dict] = []   # per-wave records not yet
        self._stream_lock = threading.Lock()    # shipped on a heartbeat
        self._slow_ranks: Optional[Dict[int, float]] = None
        self._progress = 0           # monotonic dispatch counter carried
                                     # by heartbeats: the supervisor's
                                     # hang detection watches it — a hung
                                     # trainer keeps BEATING (separate
                                     # thread) but stops PROGRESSING
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------
    def run(self) -> None:
        self.chan.send({"type": "hello"})
        cfg = self.chan.recv()
        assert cfg.get("type") == "config", cfg
        self.cfg_msg = cfg
        ranks = cfg.get("ranks") or []
        # give this process its own trace/recorder lane, so merged
        # cross-worker postmortems tell the agents apart
        lane = f"worker[{ranks[0]}..{ranks[-1]}]" if ranks else "worker"
        obs_configure(trace_process=lane,
                      trace_pid=(ranks[0] + 1) if ranks else None)
        get_recorder().process = lane
        get_recorder().record("config", ranks=list(ranks),
                              hdp=cfg.get("hdp"), serve=bool(cfg.get("serve")))
        self._start_heartbeat(cfg.get("heartbeat_interval", 0.5))
        try:
            if cfg.get("serve"):
                self._serve_loop(cfg)
                return
            self._build_trainer(hdp=cfg["hdp"], ranks=cfg["ranks"],
                                ckpt_owner=cfg["ckpt_owner"],
                                resume_step=cfg.get("resume_step", 0))
            self.chan.send({"type": "ready", "step": self.trainer.step})
            while True:
                try:
                    self._step_once()
                except Reconfigure as rc:
                    m = rc.msg
                    get_recorder().record("reconfig", hdp=m.get("hdp"),
                                          ranks=list(m.get("ranks", [])),
                                          resume_step=m.get("resume_step"))
                    self._remap_slow_ranks(m.get("rank_map"))
                    self._build_trainer(hdp=m["hdp"], ranks=m["ranks"],
                                        ckpt_owner=m["ckpt_owner"],
                                        resume_step=m["resume_step"])
                    self.chan.send({"type": "ready",
                                    "step": self.trainer.step})
                except Shutdown:
                    self._final_checkpoint()
                    self.chan.send({"type": "bye"})
                    return
        except BaseException as e:
            # postmortem before the process dies: what the agent was
            # doing in the seconds before the loop blew up
            get_recorder().record("worker_uncaught", exc=repr(e))
            get_recorder().dump("worker_uncaught")
            raise
        finally:
            self._hb_stop.set()
            self._export_trace()
            self.chan.close()

    def _export_trace(self) -> None:
        """On exit, write this process's Chrome trace into
        ``$REPRO_TRACE_DIR`` (one file per agent, named by its lane) —
        the per-process input set `repro.obs.analyze` merges into the
        cluster timeline.  Never raises: a trace-export failure must
        not mask whatever ended the agent loop."""
        tdir = os.environ.get("REPRO_TRACE_DIR")
        tr = get_tracer()
        if not tdir or not tr.enabled:
            return
        try:
            os.makedirs(tdir, exist_ok=True)
            safe = "".join(c if c.isalnum() or c in "-_" else "_"
                           for c in tr.process)[:48]
            tr.to_chrome(os.path.join(
                tdir, f"trace_{safe}_{os.getpid()}.json"))
        except OSError:
            pass

    def _start_heartbeat(self, interval: float) -> None:
        def beat():
            get_tracer().set_thread_name("heartbeat")
            while not self._hb_stop.wait(interval):
                with self._stream_lock:
                    pending, self._stream_pending = \
                        self._stream_pending, []
                try:
                    # per-WAVE telemetry rides every beat (not only the
                    # end-of-step step_done): the controller sees dispatch
                    # progress mid-step, double-stamped for cross-worker
                    # alignment
                    self.chan.send({"type": "heartbeat",
                                    "progress": self._progress,
                                    "t_mono": monotime(),
                                    "t_wall": time.time(),
                                    "telemetry": pending})
                except (OSError, EOFError):
                    return
        self._hb_thread = threading.Thread(target=beat, daemon=True,
                                           name="heartbeat")
        self._hb_thread.start()

    # -- construction --------------------------------------------------
    def _build_trainer(self, *, hdp: int, ranks: List[int],
                       ckpt_owner: bool, resume_step: int) -> None:
        import jax
        self._progress += 1          # build/rebuild is forward motion
        cfg = self.cfg_msg
        if self.trainer is not None and self.trainer.ckpt is not None:
            # reconfig path: an async save from the pre-shrink trajectory
            # may still be writing — joining it before the new trainer
            # (fresh CheckpointManager, same dir) can touch the same
            # step_<N>/.tmp paths prevents torn or stale-wins races
            self.trainer.ckpt.wait()
        self.ranks = list(ranks)
        spec = cfg["spec"].replace(hdp=hdp, rank_speed=None)
        tp = int(cfg.get("tp", 1))
        stages = spec.num_stages
        need = hdp * tp * max(stages, 1)
        assert need <= len(jax.devices()), \
            (need, len(jax.devices()), "worker mesh exceeds local devices "
             "(launcher sets --xla_force_host_platform_device_count)")
        if stages > 1:
            mesh = make_pipeline_mesh(stages, hdp, tp)
            rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                         stage_axis="stage", **cfg.get("runtime_kw", {}))
        else:
            mesh = compat.make_mesh((hdp, tp), ("data", "model"))
            rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                         **cfg.get("runtime_kw", {}))
        compat.set_mesh(mesh)
        client = RemotePlanClient(cfg["dataset"], spec, self.chan,
                                  on_state=self._on_state)
        opt = AdamWConfig(**{"total_steps": int(cfg.get("steps", 10)),
                             **cfg.get("opt_kw", {})})
        tcfg = TrainerConfig(capacity=spec.capacity,
                             ckpt_dir=cfg.get("ckpt_dir"),
                             ckpt_every=int(cfg.get("ckpt_every", 5)),
                             ckpt_save=bool(ckpt_owner),
                             max_round_waves=int(
                                 cfg.get("max_round_waves", 0)),
                             sched_async=True,   # consume shipped buffers
                             calibrate=False,    # controller calibrates
                             numerics_guard=bool(
                                 cfg.get("numerics_guard", True)),
                             nan_fault=cfg.get("nan_fault"))
        self.trainer = Trainer(cfg["model"], rt, opt, client, tcfg,
                               seed=int(cfg.get("seed", 0)))
        self.trainer.telemetry_fn = self._on_dispatch
        if self._slow_ranks is None:
            self._slow_ranks = cfg.get("slow_ranks")
        self._install_fault_injection(self._slow_ranks)
        if resume_step:
            p, o, dstate = self.trainer.ckpt.restore(
                resume_step, self.trainer.params, self.trainer.opt_state)
            self.trainer.params, self.trainer.opt_state = p, o
            self.trainer.step = int(dstate["step"])

    def _remap_slow_ranks(self, rank_map) -> None:
        """Elastic shrink renumbers the axis: ``rank_map[i]`` is the old
        global rank now at new rank i.  The drill's slowdown follows the
        physical rank — keys remap (and compose across repeated
        shrinks)."""
        if not self._slow_ranks or not rank_map:
            return
        self._slow_ranks = {new: self._slow_ranks[old]
                            for new, old in enumerate(rank_map)
                            if old in self._slow_ranks}

    def _install_fault_injection(self, slow_ranks) -> None:
        """Straggler drill: a fake per-rank clock (rank r runs ``factor``×
        slower) exercises the telemetry→calibrator→re-plan loop without
        real slow hardware."""
        if not slow_ranks:
            return
        slow = {int(r): float(f) for r, f in slow_ranks.items()}

        def clock(waves):
            waves = waves if isinstance(waves, list) else [waves]
            costs = np.sum([np.asarray(w.costs) for w in waves], axis=0)
            speed = np.ones_like(costs)
            for r, f in slow.items():
                if r < len(speed):
                    speed[r] = 1.0 / f
            return costs / speed
        self.trainer.wave_time_fn = clock

    # -- per-step hooks ------------------------------------------------
    def _on_state(self, state) -> None:
        if state is not None:
            self.trainer.extra_data_state = state

    def _on_dispatch(self, waves, measured, fresh: bool,
                     wall_s: Optional[float] = None) -> None:
        """One dispatched wave (or pipelined round): record the wall times
        of the ranks this worker owns (`make_telemetry_record`).  The
        record lands in two places — ``_telemetry``, the authoritative
        end-of-step batch `_step_once` ships with step_done (the
        calibrator's input), and ``_stream_pending``, drained onto the
        next heartbeat frame for mid-step controller visibility."""
        self._progress += 1          # hang detection: heartbeats carry it
        rec = make_telemetry_record(
            self.ranks, measured, fresh,
            step=self.trainer.step if self.trainer is not None else None,
            wall_s=wall_s)
        if self.trainer is not None \
                and self.trainer.last_ledger_record is not None:
            # bytes ledger (obs/ledger.py): the dispatch's predicted/
            # measured byte record rides the same wire frames, so the
            # controller folds a fleet ledger out of heartbeats
            rec["ledger"] = self.trainer.last_ledger_record
        if self.trainer is not None and self.trainer.last_wave_findings:
            # numerics findings (obs/numerics.py) fire MID-step: they
            # ride the streamed telemetry so the controller's numerics
            # channel sees a non-finite wave before the step completes
            rec["numerics"] = {
                "step": self.trainer.step,
                "findings": list(self.trainer.last_wave_findings)}
        self._telemetry.append(rec)
        with self._stream_lock:
            self._stream_pending.append(rec)

    def _step_once(self) -> None:
        self._telemetry = []
        rec = self.trainer.train_step()
        self._progress += 1
        keys = [k for k in self.trainer._exec_cache if k[0] != "pp"]
        self.chan.send({"type": "step_done", "step": rec["step"] - 1,
                        "loss": rec["loss"],
                        "grad_norm": rec["grad_norm"],
                        "t_mono": monotime(), "t_wall": time.time(),
                        "keys": keys, "telemetry": self._telemetry,
                        "numerics": self.trainer.last_numerics})

    # -- serve mode ----------------------------------------------------
    def _serve_loop(self, cfg: dict) -> None:
        """Serve under controller command: build one ServeEngine over the
        local mesh, then pump requests in and results out.  A reader
        thread feeds an inbox (the channel's single-reader contract) so
        the engine loop never blocks on the wire while slots are live;
        the heartbeat's progress counter advances per engine step, so
        the controller's hang detection covers serving too."""
        import queue as _q

        import jax

        from repro.models.transformer import init_params
        from repro.serve import ServeConfig, ServeEngine

        self._progress += 1
        spec = cfg["spec"].replace(hdp=cfg["hdp"], rank_speed=None)
        tp = int(cfg.get("tp", 1))
        need = spec.hdp * tp
        assert need <= len(jax.devices()), (need, len(jax.devices()))
        mesh = compat.make_mesh((spec.hdp, tp), ("data", "model"))
        rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                     **cfg.get("runtime_kw", {}))
        compat.set_mesh(mesh)
        params = init_params(jax.random.PRNGKey(int(cfg.get("seed", 0))),
                             cfg["model"], rt)
        engine = ServeEngine(params, cfg["model"], rt,
                             ServeConfig(**cfg["serve"]))
        self._progress += 1
        self.chan.send({"type": "ready", "step": 0})

        inbox: "_q.Queue" = _q.Queue()

        def reader():
            try:
                while True:
                    inbox.put(self.chan.recv())
            except (EOFError, OSError):
                inbox.put(None)

        threading.Thread(target=reader, daemon=True).start()
        rid_to_req: Dict[int, int] = {}
        while True:
            # ingest pending traffic; block only when the slab is idle
            while True:
                try:
                    if engine.pool.n_open == 0:
                        msg = inbox.get(timeout=0.25)
                    else:
                        msg = inbox.get_nowait()
                except _q.Empty:
                    if engine.pool.n_open == 0:
                        continue
                    break
                if msg is None:
                    return                    # controller gone
                mtype = msg.get("type")
                if mtype == "shutdown":
                    self.chan.send({"type": "bye"})
                    return
                if mtype == "request":
                    rid = engine.submit(np.asarray(msg["prompt"], np.int32),
                                        int(msg["max_new_tokens"]))
                    rid_to_req[rid] = msg["req"]
            finished = engine.step()
            self._progress += 1
            for req in finished:
                self.chan.send({"type": "result",
                                "req": rid_to_req.pop(req.rid),
                                "tokens": [int(t) for t in req.generated],
                                "telemetry": req.telemetry()})

    def _final_checkpoint(self) -> None:
        tr = self.trainer
        if tr is not None and tr.ckpt is not None and tr.tcfg.ckpt_save:
            tr.ckpt.save(tr.step, tr.params, tr.opt_state,
                         tr.data_state(), block=True)
            tr.ckpt.wait()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--addr", required=True,
                    help="controller address, HOST:PORT")
    ap.add_argument("--connect-timeout", type=float, default=120.0)
    args = ap.parse_args()
    WorkerAgent(args.addr, connect_timeout=args.connect_timeout).run()


if __name__ == "__main__":
    main()
