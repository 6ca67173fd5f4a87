"""The training loop: HDP waves + gradient accumulation + fault tolerance.

Per step (paper Fig. 7): the GlobalScheduler plans the global batch through
the unified planner API (`repro.core.planner.plan` — Alg. 1/2 behind one
validated entry point); each wave dispatches through a per-(composition,
c_mult, offload) jitted executable (the compile cache is ByteScale's
NCCL-group cache analogue); gradients accumulate with token-level loss
scaling and the optimizer applies once (Eq. 2 — bit-equivalent to plain
DP).  On a mesh with a stage axis (Runtime.num_stages > 1) the wave queue
instead runs through the pipelined executor: waves group into rounds of
like (composition, c_mult, offload) and each round executes the wavefront
microbatch schedule of parallel/pipeline.py, each wave one pipeline
microbatch (PP-Balance pairs with this path via TrainerConfig.mode="pp").

Fault tolerance: periodic async checkpoints (atomic + hash-verified) with
auto-resume; ``resize()`` re-plans for a different HDP size (parameters are
replicated over HDP, so elastic scaling only re-shards optimizer state);
per-rank wave-time EMAs feed the scheduler's straggler weights.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.ckpt.checkpoint import CheckpointManager
from repro.obs import numerics as numerics_mod
from repro.configs.base import ModelConfig
from repro.core.offload import offload_periods, resolve_remat
from repro.data.loader import GlobalScheduler, WaveMaterializer
from repro.obs import get_metrics, get_recorder, get_tracer, monotime
from repro.obs import ledger as ledger_mod
from repro.parallel.zero1 import zero1_bytes
from repro.sched.calibrate import OnlineCalibrator, fit_length_of
from repro.models.transformer import init_params
from repro.optim import adamw
from repro.parallel.pipeline import (assert_pipeline_ready,
                                     make_pipeline_grad_step,
                                     pipeline_rounds,
                                     pipeline_schedule_stats,
                                     rounds_splitter)
from repro.parallel.sharding import (Runtime, params_pspecs,
                                     shardings_from_pspecs)
from repro.train.train_step import make_accum_steps, named_program


@dataclass
class TrainerConfig:
    capacity: int = 512
    steps: int = 10
    ckpt_every: int = 5
    ckpt_dir: Optional[str] = None
    mode: str = "dp"                 # balance mode
    strategy: str = "balance"        # static | naive | balance
    use_offload: bool = False        # offload remat (auto-disabled when the
                                     # backend lacks a host memory space —
                                     # compat.offload_supported())
    straggler_ema: float = 0.5
    attn_impl: Optional[str] = None  # override Runtime.attn_impl per run:
                                     # "ref" (jnp oracle) | "pallas"
                                     # (ring-flash engine); None keeps the
                                     # Runtime's setting
    max_round_waves: int = 0         # pipelined executor: split rounds
                                     # longer than this many waves (0 = no
                                     # cap) to bound in-flight activations
    sched_async: bool = False        # consume pre-materialized waves from
                                     # the scheduler service's planner
                                     # thread (GlobalScheduler(sched_async=
                                     # True) pairs with this)
    calibrate: bool = True           # feed measured wave times back into
                                     # the scheduler (per-rank speeds; off
                                     # = plans depend only on the data, the
                                     # async/sync parity setting)
    recalibrate_every: int = 8       # refit Eq. 3 CostCoeffs from measured
                                     # times every N steps (0 = never)
    ckpt_save: bool = True           # False: restore-only (every ctrl
                                     # worker restores from the shared
                                     # dir, but only the rank-0 owner may
                                     # write — two processes renaming the
                                     # same step dir would race)
    numerics_guard: bool = True      # skip the optimizer apply when any
                                     # grad element is non-finite (the
                                     # fleet keeps running: counter +
                                     # advisory + flight-recorder dump
                                     # instead of a poisoned model)
    nan_fault: Optional[Dict] = None  # fault injection: {"step": k,
                                      # "wave": i} poisons that wave's
                                      # loss denominator with NaN (the
                                      # numerics drill — obs/numerics)


def _device_bytes(tree) -> int:
    """Bytes of ``tree`` that one device holds: the shard shape of each
    array under its sharding."""
    return sum(int(np.prod(x.sharding.shard_shape(x.shape)))
               * x.dtype.itemsize for x in jax.tree.leaves(tree))


class _Program:
    """One cached wave or round executable, called as
    ``(params, grad_accum, batch)``, and the remat policy it runs.

    ``jit_of(remat)`` jits the program under one policy.  The policy
    resolves at the first call, which is where the program compiles
    (`resolve_remat`): for "auto" the "none" variant is compiled for the
    call's arguments, and it runs if the compiler's memory count of it
    plus ``resident()`` (the device bytes held outside the program)
    fits the device's ``bytes_limit``; else the "full" variant runs.
    jit reuses the compile it was weighed with.  Each resolution counts
    ``trainer.remat.<policy>``."""

    def __init__(self, name: str, jit_of, remat: str, device,
                 resident, *, pipelined: bool, offload: bool):
        self.name = name
        self.remat = remat                  # resolved at the first call
        self.peak_bytes: Optional[int] = None   # the "none" variant's
        self._jit_of, self._device, self._resident = jit_of, device, resident
        self._pipelined, self._offload = pipelined, offload
        self._jits: Dict[str, Callable] = {}
        self._fn: Optional[Callable] = None

    def _jit(self, remat: str):
        if remat not in self._jits:
            self._jits[remat] = self._jit_of(remat)
        return self._jits[remat]

    def _peak(self, args) -> Optional[int]:
        """Device bytes of the "none" variant at its peak, by the
        compiler's count, plus the resident bytes; None where the
        compiler refuses it for want of memory."""
        try:
            m = self._jit("none").lower(*args).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None
        self.peak_bytes = int(
            m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + self._resident())
        return self.peak_bytes

    def resolve(self, *args) -> str:
        """Fix the policy for the program's arguments (arrays or their
        shapes), once; the first call does it."""
        if self._fn is None:
            limit = compat.device_memory_stats(self._device).get(
                "bytes_limit")
            # the weighed trace's comm records (obs/ledger) are kept
            # apart, and count only if its program runs: its first call
            # traces nothing again then
            with ledger_mod.capture() as weighed:
                self.remat = resolve_remat(
                    self.remat, limit, lambda: self._peak(args),
                    pipelined=self._pipelined, offload=self._offload)
            if self.remat == "none":
                for kind, nbytes in weighed.items():
                    ledger_mod.record_comm(kind, nbytes)
            self._fn = self._jit(self.remat)
            self._jits.clear()
            get_metrics().counter(f"trainer.remat.{self.remat}").inc()
        return self.remat

    def __call__(self, *args):
        self.resolve(*args)
        return self._fn(*args)


@dataclass
class _Wave:
    """One dispatch of a wave (or a pipelined round of waves) through
    `Trainer._dispatch`: what it runs, what its fetch and `_wave_post`
    need, and what they learn (host clock for every time)."""
    name: str                        # its span: "wave" | "round"
    idx: int
    fn: _Program
    fresh: bool                      # the program's first call
    waves: list                      # the plan's waves it runs
    composition: Tuple[int, ...]
    c_mult: int = 1
    offload_ratio: float = 0.0
    span: object = None              # its wave / round span
    metrics: Optional[Dict] = None   # the program's device outputs
    t_ret: float = 0.0               # its dispatch's return
    ready: Optional[Future] = None   # -> its completion (`_ready_at`)
    loss: float = float("nan")       # fetched
    seconds: float = 0.0             # its own time (`Trainer._fetch`)


def _ready_at(x, clock) -> float:
    """The host clock the moment device array ``x`` is ready.  Runs on
    the trainer's watcher thread, so a wave's completion is stamped
    while the step loop does host work (or stalls) after the dispatch."""
    x.block_until_ready()
    return clock()


class Trainer:
    def __init__(self, cfg: ModelConfig, rt: Runtime, opt_cfg: adamw.AdamWConfig,
                 scheduler: GlobalScheduler, tcfg: TrainerConfig,
                 seed: int = 0):
        self.cfg = cfg
        self.rt = rt
        self.opt_cfg = opt_cfg
        self.sched = scheduler
        self.tcfg = tcfg
        self.seed = seed
        assert scheduler.hdp == rt.hdp_size, \
            (scheduler.hdp, rt.hdp_size, "plan world must match mesh")
        self.offload_ok = tcfg.use_offload and compat.offload_supported()
        self._align_offload(scheduler)
        self.loader = WaveMaterializer(scheduler.ds, cfg, tcfg.capacity)
        params = init_params(jax.random.PRNGKey(seed), cfg, rt)
        # commit the parameters to the mesh by the sharding rules, so the
        # optimizer state is born there too: left on the first device,
        # every call would reshard them and the first apply would hold a
        # second copy of the AdamW state there
        self.params = jax.device_put(params, shardings_from_pspecs(
            params_pspecs(params, cfg, rt), rt.mesh))
        del params
        self.opt_state = adamw.init_state(self.params)
        self.step = 0
        self.grad_step, self.apply_step = make_accum_steps(
            cfg, rt, opt_cfg, guard=tcfg.numerics_guard)
        # the f32 gradient accumulator is donated wherever it is consumed:
        # a second live copy of it would not fit beside the optimizer
        # state of a one-chip-share model (configs/llama3_2_3b_1chip.py)
        self._apply_jit = jax.jit(self.apply_step, donate_argnums=(2,))
        self.pipelined = rt.num_stages > 1
        if self.pipelined:
            assert_pipeline_ready(cfg, rt)
            self.pipeline_grad_step = make_pipeline_grad_step(cfg, rt)
        self._exec_cache: Dict[Tuple, object] = {}
        self.ckpt = CheckpointManager(tcfg.ckpt_dir) if tcfg.ckpt_dir else None
        self.rank_times = np.zeros(rt.hdp_size)
        self.history: list = []
        self.calib = OnlineCalibrator(
            scheduler.spec.coeffs, rt.hdp_size, cfg.num_layers,
            quadratic=scheduler.spec.quadratic, ema=tcfg.straggler_ema)
        self.ledger = None           # lazy bytes ledger (obs/ledger.py):
                                     # built on the first dispatch with
                                     # the ledger on, priced from the
                                     # live plan geometry
        self.last_ledger_record = None  # ctrl-worker hook: the most
                                        # recent per-dispatch ledger
                                        # record, streamed on heartbeats
        self._ledger_meas: Dict[int, dict] = {}  # id(jitted fn) -> trace-
                                                 # time comm tally (bytes)
        self.wave_time_fn = None     # DEPRECATED fake-clock hook: replaces
                                     # the measured dispatch time (scalar
                                     # wall or per-rank vector).  New code
                                     # should run under the control plane
                                     # (repro.ctrl), where workers stream
                                     # true per-rank telemetry; the hook
                                     # stays for single-process tests.
        self.telemetry_fn = None     # ctrl-worker hook: called with
                                     # (waves, measured, fresh,
                                     # wall_s=host wall) for every
                                     # dispatch, regardless of tcfg
                                     # .calibrate — the agent streams it
                                     # to the controller (§6.1)
        self.extra_data_state = None  # ctrl-worker hook: controller-owned
                                      # scheduler/calibrator state merged
                                      # into checkpoint data_state
        # ONE monotonic clock for every telemetry/span measurement in the
        # step loop (obs.monotime = time.perf_counter); wall clock only
        # appears as the human-readable ``t_wall`` record field
        self._clock = monotime
        self._t_fetched = 0.0        # the last fetched wave's completion
        # stamps each dispatched wave's completion (`_ready_at`), in
        # dispatch order: a wave's time then ends when the chip finished
        # it, not when the host came back to read it
        self._watcher = ThreadPoolExecutor(1, "wave-watch")
        # numerics observatory: online monitor + per-step provenance
        # (obs/numerics.py).  ``last_numerics`` / ``last_wave_findings``
        # are ctrl-worker hooks: the step summary rides step_done, wave
        # findings ride the streamed per-dispatch telemetry records.
        self.numerics = numerics_mod.NumericsMonitor()
        self.last_numerics: Optional[Dict] = None
        self.last_wave_findings: list = []
        self._last_ckpt_step: Optional[int] = None
        self._numerics_dumps = 0
        self._numerics_dump_cap = 2
        self._numerics_dump_reason: Optional[str] = None
        self._attach_materializer(scheduler)
        self._publish_manifest()

    def _publish_manifest(self) -> None:
        """Land the run's reproduction recipe in the flight recorder's
        meta block, so every dump is self-describing for obs/replay."""
        NU = numerics_mod
        try:
            get_recorder().set_meta("run_manifest", {
                "model": NU.model_to_dict(self.cfg),
                "spec": NU.spec_to_dict(self.sched.spec),
                "dataset": NU.dataset_to_dict(self.sched.ds),
                "opt": dataclasses.asdict(self.opt_cfg),
                "runtime": {
                    "hdp": int(self.rt.hdp_size), "tp": int(self.rt.tp),
                    "num_stages": int(self.rt.num_stages),
                    "remat": self._remat_resolved(),
                    "remat_programs": {
                        p.name: p.remat for p in self._exec_cache.values()},
                    "kv_chunk": int(self.rt.kv_chunk),
                    "attn_impl": self.rt.attn_impl,
                    "seq_parallel": bool(self.rt.seq_parallel),
                },
                "trainer": {
                    "capacity": self.tcfg.capacity, "mode": self.tcfg.mode,
                    "strategy": self.tcfg.strategy,
                    "ckpt_dir": self.tcfg.ckpt_dir,
                    "ckpt_every": self.tcfg.ckpt_every,
                    "max_round_waves": self.tcfg.max_round_waves,
                    "attn_impl": self.tcfg.attn_impl,
                    "numerics_guard": self.tcfg.numerics_guard,
                    "nan_fault": self.tcfg.nan_fault,
                },
                "seed": int(self.seed),
            })
        except Exception:       # manifest is best-effort observability
            pass

    # ------------------------------------------------------------------
    def _attach_materializer(self, scheduler) -> None:
        """Materialize-ahead: the planner thread pre-builds upcoming
        steps' buffers — per-wave buffers on the non-PP path, stacked
        [M, ...] round buffers on the pipelined path (`rounds_splitter`
        is the one round-split contract shared with the executor)."""
        if self.tcfg.sched_async and hasattr(scheduler, "service"):
            scheduler.service.attach_materializer(
                self.loader,
                rounds_fn=rounds_splitter(self.tcfg.max_round_waves)
                if self.pipelined else None)

    def _align_offload(self, scheduler: GlobalScheduler):
        """Keep plan and execution consistent: when waves cannot offload
        (no host memory space, or disabled in the TrainerConfig), the
        scheduler must not size groups with Eq. 3's offload term either."""
        if scheduler.spec.use_offload and not self.offload_ok:
            scheduler.spec = scheduler.spec.replace(use_offload=False)

    def _remat_resolved(self) -> str:
        """The manifest's remat: the one policy every cached program
        runs, else the configured value (``remat_programs`` then names
        each program's)."""
        policies = {p.remat for p in self._exec_cache.values()}
        return policies.pop() if len(policies) == 1 else self.rt.remat

    def _program(self, kind, composition, c_mult, offload_ratio, step_fn,
                 n_waves: Optional[int] = None) -> _Program:
        """A wave program, or with ``n_waves`` a pipelined round of that
        many waves; its remat policy resolves at its first call
        (`_Program`) against the first local device's ``bytes_limit``,
        with the AdamW state resident beside it."""
        import dataclasses as dc
        rt_wave = self.rt.with_composition(composition)
        if self.tcfg.attn_impl is not None:
            rt_wave = dc.replace(rt_wave, attn_impl=self.tcfg.attn_impl)
        if self.offload_ok and offload_ratio > 0:
            rt_wave = dc.replace(
                rt_wave, remat="offload",
                # stage-aware count: under PP the stage vmap applies the
                # window per stage, so the static count must be sized
                # against the stage-local period window (core/offload.py)
                offload_periods=offload_periods(self.cfg, offload_ratio,
                                                self.rt.num_stages))

        def jit_of(remat):
            return jax.jit(named_program(kind, composition, step_fn,
                                         dc.replace(rt_wave, remat=remat)),
                           donate_argnums=(1,))
        name = "_".join([kind] + [str(int(c)) for c in composition]
                        + [f"c{c_mult}", f"r{round(offload_ratio, 2)}"]
                        + ([f"m{n_waves}"] if n_waves else []))
        device = next(d for d in self.rt.mesh.devices.flat
                      if d.process_index == jax.process_index())
        return _Program(name, jit_of, rt_wave.remat, device,
                        lambda: _device_bytes(self.opt_state),
                        pipelined=n_waves is not None,
                        offload=offload_ratio > 0)

    def _wave_fn(self, composition, c_mult, offload_ratio):
        """-> (jitted executable, fresh) — ``fresh`` marks a cache miss
        (the dispatch will pay a compile; the calibrator skips it).  The
        executable is named for its composition (`named_program`:
        ``jit_wave_2_1_1`` on the device)."""
        key = (composition, c_mult, round(offload_ratio, 2))
        fresh = key not in self._exec_cache
        get_metrics().counter("trainer.compile_miss" if fresh
                              else "trainer.compile_hit").inc()
        if fresh:
            self._exec_cache[key] = self._program(
                "wave", composition, c_mult, offload_ratio, self.grad_step)
        return self._exec_cache[key], fresh

    def _round_fn(self, composition, c_mult, offload_ratio, n_waves: int):
        """Pipelined executable for a round of ``n_waves`` like waves —
        the compile-cache analogue of `_wave_fn` with the microbatch
        stream length as part of the key."""
        key = ("pp", composition, c_mult, round(offload_ratio, 2), n_waves)
        fresh = key not in self._exec_cache
        get_metrics().counter("trainer.compile_miss" if fresh
                              else "trainer.compile_hit").inc()
        if fresh:
            self._exec_cache[key] = self._program(
                "round", composition, c_mult, offload_ratio,
                self.pipeline_grad_step, n_waves=n_waves)
        return self._exec_cache[key], fresh

    def resume_if_possible(self):
        """Resume from the newest checkpoint that passes integrity —
        a corrupt/torn newest dir (mid-save kill) falls back to the last
        good one instead of raising.  Scheduler/calibrator state saved in
        ``data_state`` restores warm (straggler speeds, templates, blended
        coeffs) when the world size still matches."""
        if self.ckpt is None:
            return False
        res = self.ckpt.restore_latest(self.params, self.opt_state)
        if res is None:
            return False
        _, self.params, self.opt_state, data_state = res
        self.step = int(data_state["step"])
        self._last_ckpt_step = self.step
        self.load_ctrl_state(data_state)
        return True

    def data_state(self) -> Dict:
        """Checkpoint data_state: the step cursor plus the scheduling
        brain's warm state.  Under the control plane the worker saves the
        CONTROLLER's state (shipped with each plan — `extra_data_state`);
        single-process runs save their own service/calibrator."""
        ds: Dict = {"step": self.step}
        if self.extra_data_state is not None:
            ds.update(self.extra_data_state)
            return ds
        ds["calib"] = self.calib.state_dict()
        if hasattr(self.sched, "service"):
            ds["sched"] = self.sched.service.state_dict()
        return ds

    def load_ctrl_state(self, data_state: Dict) -> None:
        """Warm-start the calibrator and scheduler service from a
        checkpoint's data_state (no-ops on geometry mismatch)."""
        calib_state = data_state.get("calib")
        if calib_state:
            self.calib.load_state(calib_state)
        sched_state = data_state.get("sched")
        if sched_state and hasattr(self.sched, "service"):
            self.sched.service.load_state(sched_state)
            if self.tcfg.calibrate and self.calib.n_observed > 0:
                self.sched.update_rank_speed(self.calib.rank_speed())

    def resize(self, new_hdp_scheduler: GlobalScheduler):
        """Elastic rescale: params/opt are HDP-replicated; only the plan
        changes.  (On hardware this follows a mesh re-init + ZeRO reshard
        via the checkpoint restore path.)"""
        if new_hdp_scheduler is not self.sched \
                and hasattr(self.sched, "stop"):
            self.sched.stop()   # old planner thread + pre-built buffers
        self.sched = new_hdp_scheduler
        self._align_offload(new_hdp_scheduler)
        self.rank_times = np.zeros(new_hdp_scheduler.hdp)
        self.calib = OnlineCalibrator(
            new_hdp_scheduler.spec.coeffs, new_hdp_scheduler.hdp,
            self.cfg.num_layers, quadratic=new_hdp_scheduler.spec.quadratic,
            ema=self.tcfg.straggler_ema)
        self._attach_materializer(new_hdp_scheduler)
        self._publish_manifest()    # the spec (hdp) changed

    # ------------------------------------------------------------------
    def _observe(self, waves, measured, fresh_compile: bool,
                 modeled: bool = False, wall_s: Optional[float] = None):
        """Feed one measured dispatch (a wave, or a pipelined round's
        waves) to the telemetry hook and the local calibrator.
        ``measured`` is the SPMD wall time (float) or a per-rank time
        vector (the `wave_time_fn` fault-injection clock supplies one).
        The telemetry hook (ctrl worker agent) sees EVERY dispatch with
        the TRUE ``fresh`` flag and the TRUE host wall ``wall_s``, the
        dispatch's own time on the host clock (`_fetch`) — downstream
        consumers (anomaly gap cursor, straggler join) must know a
        compile sits in the cadence and how long the chip ran the
        dispatch, even when ``measured`` itself is a modeled vector.
        ``modeled`` times carry no compile pollution, so the local
        calibrator ingests them on fresh waves too."""
        if self.telemetry_fn is not None:
            self.telemetry_fn(waves, measured, fresh_compile,
                              wall_s=wall_s)
        if (fresh_compile and not modeled) or not self.tcfg.calibrate:
            return
        costs = np.zeros(self.sched.hdp)
        for w in waves:
            costs += np.asarray(w.costs)
        kw = dict(fit_length=fit_length_of(waves))
        if np.ndim(measured) > 0:
            self.calib.observe(costs, rank_seconds=measured, **kw)
        else:
            self.calib.observe(costs, seconds=float(measured), **kw)

    def _ensure_ledger(self):
        """Bytes ledger (obs/ledger.py), built lazily on the first
        dispatch with the ledger on (REPRO_LEDGER or `obs.configure
        (ledger=True)`) — and rebuilt after an elastic resize (the HDP
        world size prices ZeRO-1 collectives and the optimizer-shard
        term of the HBM watermark).  Returns None when the ledger is off
        (zero cost on the disabled path)."""
        if not ledger_mod.ledger_enabled():
            return None
        if self.ledger is None or self.ledger.hdp != self.sched.hdp:
            self.ledger = ledger_mod.Ledger(
                self.cfg, capacity=self.tcfg.capacity, hdp=self.sched.hdp,
                num_stages=self.rt.num_stages, tp=self.rt.tp,
                coeffs=self.sched.spec.coeffs,
                offload_active=self.offload_ok,
                pos_width=3 if self.cfg.pos_embed == "mrope" else 1)
            self.ledger.set_step_bytes(zero1_bytes(self.params, self.rt))
        return self.ledger

    def _dispatch(self, tr, mx, step: int, prev: Optional[_Wave],
                  w: _Wave, grads, batch, *, last: bool):
        """Dispatch one wave (or pipelined round) ``w`` under its span and
        fetch the losses that are due, each under a ``wave_wait`` span
        stamped with the ``idx`` of the wave it fetches; each fetched
        wave then gets its ``wave_post``, in wave order.  -> (grads, the
        wave still in flight or None, the fetched waves).

        A warm wave's loss is read one wave late: ``prev`` (the wave
        before, still in flight) is fetched after ``w`` is dispatched,
        so the chip runs ``w`` while the host prepares the next wave.
        The gradient accumulator flows from wave to wave by donation,
        so the chip runs the waves in the same order either way.  A
        fresh program (its first call compiles and resolves its remat
        policy) fetches ``prev`` before it and its own loss at once,
        under the nested ``compile`` span; the step's last wave fetches
        its own before the apply is dispatched.

        Bytes ledger: a fresh compile's trace runs under
        ``ledger.capture()``, harvesting the instrumented comm sites'
        static byte counts into a per-executable tally; warm dispatches
        re-stamp the cached tally (`_wave_post`)."""
        led = self._ensure_ledger()
        capture = w.fresh and led is not None
        done = []
        with tr.span(w.name, step=step, idx=w.idx,
                     composition=w.composition, fresh=w.fresh) as sp:
            w.span = sp
            if w.fresh and prev is not None:
                done.append(self._fetch(tr, mx, step, prev, deferred=False))
                prev = None
            with (tr.span("compile", step=step, idx=w.idx,
                          composition=w.composition)
                  if w.fresh else contextlib.nullcontext()) as csp:
                with tr.span("wave_dispatch", step=step, idx=w.idx), \
                        (ledger_mod.capture() if capture
                         else contextlib.nullcontext()) as tally:
                    grads, w.metrics = w.fn(self.params, grads, batch)
                w.t_ret = self._clock()
                w.ready = self._watcher.submit(
                    _ready_at, w.metrics["loss"], self._clock)
                if capture:
                    self._ledger_meas[id(w.fn)] = dict(tally)
                if prev is not None:
                    done.append(self._fetch(tr, mx, step, prev, deferred=True))
                if w.fresh or last:
                    done.append(self._fetch(tr, mx, step, w, deferred=False))
                if w.fresh:
                    # the policy the first call resolved (`_Program`)
                    csp.set("remat", w.fn.remat)
                    csp.set("remat_peak_bytes", w.fn.peak_bytes)
            if w.fresh:
                self._publish_manifest()
        for d in done:
            with tr.span("wave_post", step=step, idx=d.idx):
                self._wave_post(mx, d)
        return grads, None if w.fresh or last else w, done

    def _fetch(self, tr, mx, step: int, w: _Wave,
               deferred: bool) -> _Wave:
        """Block on one dispatched wave's loss (the ``wave_wait`` span)
        and time it: ``w.seconds`` is its completion, as the watcher
        stamped it, less the later of its dispatch's return and the
        previous wave's completion.  That is the wave's own time on the
        host clock: host work or a stall between its completion and
        this fetch stays out of it, in the gap before the next record.
        Counts ``trainer.wave_fetch.deferred`` where the next wave was
        dispatched first, else ``.immediate``."""
        with tr.span("wave_wait", step=step, idx=w.idx):
            t_done = w.ready.result()
            w.loss = float(w.metrics["loss"])
        w.seconds = t_done - max(w.t_ret, self._t_fetched)
        self._t_fetched = t_done
        mx.counter("trainer.wave_fetch."
                   + ("deferred" if deferred else "immediate")).inc()
        return w

    # -- numerics observatory hooks ------------------------------------

    def _nan_fault_hits(self, idx: int) -> bool:
        nf = self.tcfg.nan_fault
        return bool(nf) and self.step == int(nf.get("step", -1)) \
            and idx == int(nf.get("wave", 0))

    def _note_findings(self, findings: list, mx) -> None:
        """Land monitor findings in the ring + metrics the moment they
        fire (mid-step for wave findings — the worker streams them), and
        arm a bounded flight-recorder dump on severe ones.  The dump
        itself waits until the step's provenance record has landed, so
        it always carries its own reproduction recipe."""
        if not findings:
            return
        rec = get_recorder()
        for f in findings:
            mx.counter("numerics.findings").inc()
            rec.record("numerics_finding",
                       **{k: v for k, v in f.items() if k != "kind"})
            if f["severity"] >= numerics_mod.NONFINITE_SEVERITY \
                    and self._numerics_dumps < self._numerics_dump_cap \
                    and self._numerics_dump_reason is None:
                self._numerics_dump_reason = f"numerics_{f['reason']}"

    def train_step(self) -> Dict:
        """One global batch: plan; per wave (or pipelined round)
        materialize its buffers, copy them in, dispatch, then fetch and
        observe the wave before it (`_dispatch`); then one optimizer
        apply.  With tracing on, every host stretch of the step is a span
        (README, "Observability") inside one ``train_step`` span, which
        also marks the step on the profiler's ``Steps`` line."""
        tr = get_tracer()
        with tr.step_span("train_step", self.step, "train"):
            return self._train_step(tr, self.step)

    def _train_step(self, tr, step: int) -> Dict:
        mx = get_metrics()
        t0 = self._clock()
        n_find0 = len(self.numerics.findings)
        with tr.span("plan", step=step):
            if self.tcfg.sched_async and hasattr(self.sched, "get_step"):
                plan, pre_waves = self.sched.get_step(self.step)
            else:
                plan, pre_waves = self.sched.plan_step(self.step), None
        denom = float(plan.denom)
        with tr.span("grads_init", step=step):
            grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 self.params)
        losses = []
        prev = None     # the wave in flight whose loss is still unread
        n_rounds = None
        if self.pipelined:
            # pipelined executor: the wave queue runs as rounds of like
            # waves, each round one wavefront schedule (parallel/pipeline);
            # round r+1 materializes in the background while r executes
            rounds = pipeline_rounds(plan, self.tcfg.max_round_waves)
            n_rounds = len(rounds)
            # pre_waves: stacked [M, ...] round buffers the scheduler
            # service pre-built (materialize-ahead; its rounds_fn mirrors
            # this split).  Fallback is the prefetch iterator, driven
            # directly (not zip) so it drains fully — its epilogue joins
            # the producer thread and re-raises any captured error
            round_iter = iter(pre_waves) if pre_waves is not None \
                else self.loader.iter_rounds(self.step, plan, rounds)
            for i in range(len(rounds)):
                # the materialize span measures the wait for the round's
                # buffers (near-zero when materialize-ahead got there)
                with tr.span("materialize", step=step, idx=i):
                    stacked = next(round_iter)
                rd = rounds[i]
                with tr.span("h2d", step=step, idx=i):
                    batch = self._to_device(stacked, i, denom)
                fn, fresh = self._round_fn(rd.composition, rd.c_mult,
                                           rd.offload_ratio,
                                           len(rd.wave_ids))
                w = _Wave("round", i, fn, fresh,
                          [plan.waves[j] for j in rd.wave_ids],
                          rd.composition, rd.c_mult, rd.offload_ratio)
                grads, prev, done = self._dispatch(
                    tr, mx, step, prev, w, grads, batch,
                    last=i == len(rounds) - 1)
                losses += [d.loss for d in done]
            for _ in round_iter:        # drain the prefetch epilogue so
                pass                    # producer errors still surface
        else:
            wave_iter = iter(pre_waves) if pre_waves is not None \
                else self.loader.iter_step(self.step, plan)
            for i in range(len(plan.waves)):
                with tr.span("materialize", step=step, idx=i):
                    lw = next(wave_iter)
                with tr.span("h2d", step=step, idx=i):
                    batch = self._to_device(lw.batch, i, denom)
                fn, fresh = self._wave_fn(lw.composition, lw.c_mult,
                                          lw.offload_ratio)
                w = _Wave("wave", i, fn, fresh, [plan.waves[i]],
                          lw.composition, lw.c_mult, lw.offload_ratio)
                grads, prev, done = self._dispatch(
                    tr, mx, step, prev, w, grads, batch,
                    last=i == len(plan.waves) - 1)
                losses += [d.loss for d in done]
            for _ in wave_iter:         # drain the prefetch epilogue so
                pass                    # producer errors still surface
        with tr.span("apply", step=step):
            with tr.span("apply_dispatch", step=step):
                self.params, self.opt_state, om = self._apply_jit(
                    self.params, self.opt_state, grads)
            # ONE host fetch for the whole fused sentinel summary
            # (grad_norm + per-group norms + non-finite count + applied
            # flag) — the step pays the same single sync it used to pay
            # for grad_norm alone; it blocks until the apply has run
            with tr.span("apply_fetch", step=step):
                om = {k: np.asarray(v).item()
                      for k, v in jax.device_get(om).items()}
        with tr.span("step_end", step=step):
            rec = self._end_step(plan, losses, om, t0, n_find0, n_rounds,
                                 mx)
        if self.ckpt and self.tcfg.ckpt_save \
                and self.step % self.tcfg.ckpt_every == 0:
            with tr.span("checkpoint", step=step):
                self.ckpt.save(self.step, self.params, self.opt_state,
                               self.data_state())
                self._last_ckpt_step = self.step
        return rec

    def _to_device(self, host_batch, idx: int, denom: float) -> Dict:
        """A wave's (or round's) host buffers and the step's loss
        denominator as device arrays (the ``h2d`` span)."""
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        batch["denom"] = jnp.float32(
            float("nan") if self._nan_fault_hits(idx) else denom)
        return batch

    def _wave_post(self, mx, w: _Wave) -> None:
        """The host's work after one fetched dispatch (the ``wave_post``
        span): the numerics monitor, the dispatch histogram and
        `_observe`, all fed the wave's own time ``w.seconds``.  The
        `wave_time_fn` hook prices a round's waves, or one wave.

        With the ledger on, lands the dispatch's predicted/measured
        record (plus an allocator HBM peak sample where the backend
        exposes one) on the ledger, on the wave's span (its event keeps
        the span's ``args``, so stamps set after a late fetch still
        land) and on ``last_ledger_record`` for the ctrl worker's
        heartbeat."""
        self.last_wave_findings = \
            self.numerics.observe_wave(self.step, w.idx, w.loss)
        self._note_findings(self.last_wave_findings, mx)
        mx.histogram("trainer.dispatch_s").observe(w.seconds)
        led = self._ensure_ledger()
        if led is not None:
            hbm = compat.device_memory_stats().get("peak_bytes_in_use")
            rec = led.record_dispatch(
                step=self.step, idx=w.idx, kind=w.name,
                composition=w.composition, c_mult=w.c_mult,
                offload_ratio=w.offload_ratio, n_waves=len(w.waves),
                fresh=w.fresh, measured=self._ledger_meas.get(id(w.fn)),
                hbm_peak=hbm)
            self.last_ledger_record = rec
            w.span.set("bytes_pred", rec["pred"])
            mx.counter("comm.pred_bytes").inc(sum(rec["pred"].values()))
            mx.gauge("mem.hbm_pred_peak").set(float(rec["hbm_pred"]))
            if hbm is not None:
                mx.gauge("mem.hbm_meas_peak").set(float(hbm))
            if "meas" in rec:
                w.span.set("bytes_meas", rec["meas"])
                mx.counter("comm.meas_bytes").inc(
                    sum(rec["meas"].values()))
                self.calib.observe_bytes(sum(rec["pred"].values()),
                                         sum(rec["meas"].values()))
                mx.gauge("comm.residual").set(led.comm_residual())
        dt = w.seconds
        if self.wave_time_fn is not None:
            dt = self.wave_time_fn(w.waves if self.pipelined
                                   else w.waves[0])
        self._observe(w.waves, dt, w.fresh,
                      modeled=self.wave_time_fn is not None,
                      wall_s=w.seconds)

    def _end_step(self, plan, losses, om, t0: float, n_find0: int,
                  n_rounds: Optional[int], mx) -> Dict:
        """Close the step (the ``step_end`` span): straggler feedback,
        compiled keys to the scheduler, the step record, the numerics
        monitor and provenance, and the per-step metrics export."""
        rec_extra = {}
        if n_rounds is not None:
            sched_stats = pipeline_schedule_stats(
                plan, self.rt.num_stages, self.tcfg.max_round_waves)
            rec_extra = {"rounds": n_rounds,
                         "bubble_frac_pipeline":
                             sched_stats["bubble_frac_pipeline"]}
        # straggler feedback: *measured* per-rank speeds (the old loop
        # EMA'd the plan's own modeled costs — on a balanced plan every
        # rank looked identical and a real straggler was invisible)
        if self.tcfg.calibrate and self.calib.n_observed > 0:
            self.sched.update_rank_speed(self.calib.rank_speed())
            if self.tcfg.recalibrate_every > 0 \
                    and (self.step + 1) % self.tcfg.recalibrate_every == 0 \
                    and hasattr(self.sched, "update_coeffs"):
                refit = self.calib.coeffs()
                if refit is not None:
                    self.sched.update_coeffs(refit)
        if hasattr(self.sched, "service"):
            # compiled keys seed future windows' composition templates
            self.sched.service.warm_keys(
                [k for k in self._exec_cache if k[0] != "pp"])
        self.step += 1
        rec = {"step": self.step, "loss": float(np.sum(losses)),
               "waves": len(plan.waves),
               "compositions": [tuple(w.composition) for w in plan.waves],
               "bubble_frac": plan.stats["bubble_frac"],
               "grad_norm": float(om["grad_norm"]),
               # wall_s on the monotonic clock (same timeline as every
               # span); t_wall is the one human-readable wall stamp
               "wall_s": self._clock() - t0,
               "t_wall": time.time(), **rec_extra}
        self.history.append(rec)
        mx.counter("trainer.steps").inc()
        mx.counter("trainer.waves").inc(len(plan.waves))
        mx.gauge("trainer.loss").set(rec["loss"])
        mx.gauge("trainer.step_wall_s").set(rec["wall_s"])
        get_recorder().record("train_step", step=self.step,
                              loss=rec["loss"], waves=rec["waves"],
                              wall_s=rec["wall_s"])
        # numerics observatory: step-level monitor pass + provenance
        step_idx = self.step - 1        # the step index just executed
        self._note_findings(
            self.numerics.observe_step(step_idx, rec["loss"], om), mx)
        applied = int(om.get("applied", 1))
        if applied == 0:
            mx.counter("numerics.guard_skips").inc()
        mx.gauge("numerics.grad_nonfinite").set(
            float(om.get("grad_nonfinite", 0)))
        step_findings = self.numerics.findings[n_find0:]
        prov = numerics_mod.StepProvenance(
            step=step_idx, plan_hash=numerics_mod.plan_fingerprint(plan),
            denom=int(plan.denom), n_waves=len(plan.waves),
            wave_losses=[float(l) for l in losses],
            sentinels={k: v for k, v in om.items() if k != "applied"},
            applied=applied, ckpt_step=self._last_ckpt_step,
            sched_prov=plan.stats.get("sched_prov"),
            n_seqs=plan.stats.get("lengths"),
            nan_fault=self.tcfg.nan_fault
            if self.tcfg.nan_fault
            and int(self.tcfg.nan_fault.get("step", -1)) == step_idx
            else None)
        get_recorder().record("step_provenance", **prov.to_record())
        self.last_numerics = {
            "step": step_idx, "loss": rec["loss"],
            "grad_norm": rec["grad_norm"],
            "grad_nonfinite": int(om.get("grad_nonfinite", 0)),
            "applied": applied, "findings": step_findings}
        if self._numerics_dump_reason is not None:
            # severe finding this step: dump AFTER the provenance record
            # landed, so the dump is replayable (bounded by the cap —
            # retention in recorder.dump rotates old files regardless)
            self._numerics_dumps += 1
            get_recorder().dump(self._numerics_dump_reason)
            self._numerics_dump_reason = None
        mx.export_step(self.step)
        return rec

    def run(self, steps: Optional[int] = None):
        n = steps if steps is not None else self.tcfg.steps
        for _ in range(n):
            yield self.train_step()
        if self.ckpt and self.tcfg.ckpt_save:
            self.ckpt.save(self.step, self.params, self.opt_state,
                           self.data_state(), block=True)
            self._last_ckpt_step = self.step
            self.ckpt.wait()
