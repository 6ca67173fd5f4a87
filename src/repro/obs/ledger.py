"""Per-wave predicted/measured bytes ledger (comm + memory observability).

ByteScale's claims are claims about *bytes* — the communication optimizer
"eliminates redundant communication for short sequences" and "compresses
communication for long sequences by selective offloading" — so this module
closes the loop the time-based observability stack (spans, attribution,
goodput) leaves open: for every dispatched wave it produces a **predicted**
byte count derived purely from the plan + model config, and a **measured**
byte count tallied from the instrumented hot paths, per collective kind:

  kind           predicted from                    measured at
  -------------  --------------------------------  -------------------------
  ring           composition + KV payload model    core/ring.py ppermute site
                 (zigzag ring: steps x edges)      kernels/ring_flash.py rot
  pp             stage-roll payload x ticks        parallel/pipeline.py roll
  offload_d2h/   Eq. 3 ratio x residual-stream     models/transformer.py
  offload_h2d    bytes (continuous r)              offload split (quantized)
  zero1_*        parallel/zero1.zero1_bytes        (analytic on both sides:
                                                   XLA emits the collectives;
                                                   residual 0 by construction)

How "measured" works under jit: XLA executes the collectives, so Python
never sees per-execution transfers.  But JAX *traces* every executable
exactly once per compile, and at trace time the instrumented sites hold the
actual arrays being permuted/transferred — static shapes, static perm
tables.  A thread-local tally captures those sizes during the fresh-compile
dispatch (`capture()`), with `comm_scale(n)` contexts supplying the
multiplicity of ``lax.scan`` bodies and stage vmaps (traced once, executed
n times).  The tally is cached per executable and re-stamped on every warm
dispatch of the same key.

Accounting convention: bytes are **fleet totals** (summed over ranks — one
ppermute with E edges moves E x per-rank-payload bytes), and both sides
count the **forward-trace** traffic only: the oracle ring's backward is an
XLA transpose (invisible to Python) and the Pallas reverse ring is skipped
symmetrically, so predicted == measured stays exact on the oracle path.
Backward traffic is a documented analytic multiple (`CommModel.bwd_factor`)
applied by consumers that want wall-clock pricing, never by the ledger.

Zero-overhead contract: with tracing and ``REPRO_LEDGER`` both off, the
trainer never constructs a `Ledger` and the instrumented sites reduce to
one ``tally_active()`` check *per trace* (not per execution).
"""
from __future__ import annotations

import contextlib
import os
import threading
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

# NOTE: repro.core.offload imports only configs — safe here (instrumented
# core/model modules import this module back through repro.obs).
from repro.core import offload as OF

#: Collective kinds the tally/ledger track (zero1_* stays analytic).
COMM_KINDS = ("ring", "pp", "offload_d2h", "offload_h2d")


# ---------------------------------------------------------------------------
# enablement
# ---------------------------------------------------------------------------

_enabled = os.environ.get("REPRO_LEDGER", "") not in ("", "0", "false")


def ledger_enabled() -> bool:
    """The ledger's one enable knob (``REPRO_LEDGER=1`` or
    `obs.configure(ledger=True)`), independent of tracing: a traced run
    does the same work as an untraced one."""
    return _enabled


def set_ledger_enabled(v: bool) -> bool:
    global _enabled
    _enabled = bool(v)
    return _enabled


# ---------------------------------------------------------------------------
# trace-time tally (the "measured" side)
# ---------------------------------------------------------------------------

_TLS = threading.local()


def tally_active() -> bool:
    """Fast guard for instrumented sites: is a capture open on this
    thread?  Sites must check this before computing payload sizes so the
    un-captured trace path costs one attribute read."""
    return getattr(_TLS, "tally", None) is not None


@contextlib.contextmanager
def capture():
    """Open a tally on this thread and yield the dict it fills
    (kind -> fleet bytes).  Wrap the *first* call of a jitted executable:
    tracing happens inside it, and tracing is when the instrumented sites
    run."""
    prev = getattr(_TLS, "tally", None)
    prev_scale = getattr(_TLS, "scale", 1.0)
    tally: Dict[str, float] = {}
    _TLS.tally = tally
    _TLS.scale = 1.0
    try:
        yield tally
    finally:
        _TLS.tally = prev
        _TLS.scale = prev_scale


@contextlib.contextmanager
def comm_scale(n: float):
    """Multiply bytes recorded inside by ``n`` — the execution count of a
    region that traces once (``lax.scan`` body, stage vmap).  Nested
    scopes compound."""
    if not tally_active():
        yield
        return
    prev = _TLS.scale
    _TLS.scale = prev * float(n)
    try:
        yield
    finally:
        _TLS.scale = prev


def record_comm(kind: str, nbytes) -> None:
    """Add ``nbytes`` (x the active scale) to the open tally; no-op when
    no capture is open."""
    tally = getattr(_TLS, "tally", None)
    if tally is None:
        return
    tally[kind] = tally.get(kind, 0.0) + float(nbytes) * _TLS.scale


def tree_bytes(tree) -> int:
    """Total payload bytes of a pytree of (traced) arrays — shapes and
    dtypes are static at trace time."""
    import jax  # lazy: only instrumented trace sites reach this

    total = 0
    for leaf in jax.tree.leaves(tree):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# predicted-side byte model
# ---------------------------------------------------------------------------

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


def act_itemsize(cfg) -> int:
    """Itemsize of the activation dtype (numpy cannot parse bfloat16)."""
    return _ITEMSIZE.get(str(cfg.dtype), 4)


def attn_layer_count(cfg) -> int:
    """Layers that run ring attention (codes 'g'/'l'; SSM layers relay
    O(1) state through other collectives the ledger does not track)."""
    return sum(1 for i in range(cfg.num_layers)
               if cfg.layer_code(i) in ("g", "l"))


def ring_edges(composition: Sequence[int]) -> int:
    """ppermute edges per ring rotation: every group g > 1 contributes g
    send edges (the union-of-rings perm of `core.ring.ring_perm`)."""
    return sum(g for g in composition if g > 1)


def ring_block_bytes(cfg, tokens_per_rank: int, *, tp: int = 1,
                     kv_sharded: Optional[bool] = None) -> int:
    """Per-rank bytes of ONE carried ring block — exactly the tree both
    ring backends rotate: fused KV (or the MLA latent) [C, G_loc, W],
    k_seg [C] i32, k_pos [C] i32, and the [4] i32 block metadata.

    Must mirror the tensors `core.ring._ring_attention_local` /
    `kernels.ring_flash.ring_flash_fwd` actually build — the CPU oracle
    exactness gate (tests/test_ledger.py) pins the two together."""
    c = int(tokens_per_rank)
    if getattr(cfg, "mla", None) is not None:
        g_loc, width = 1, cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    else:
        g = cfg.num_kv_heads
        if kv_sharded is None:
            kv_sharded = tp > 1 and g % tp == 0
        g_loc = g // tp if (kv_sharded and tp > 1) else g
        width = 2 * cfg.resolved_head_dim          # fused k+v
    kv_b = c * g_loc * width * act_itemsize(cfg)
    seg_b = c * 4
    pos_b = c * 4
    meta_b = 4 * 4
    return kv_b + seg_b + pos_b + meta_b


def wave_ring_bytes(cfg, composition: Sequence[int], tokens_per_rank: int,
                    *, tp: int = 1,
                    kv_sharded: Optional[bool] = None) -> int:
    """Fleet forward-ring bytes of ONE wave dispatch: every attention
    layer runs ``max(comp) - 1`` rotations, each moving `ring_edges`
    per-rank blocks.  Zero for all-singleton compositions (short
    sequences: the redundant communication HDP eliminates)."""
    steps = max(composition) - 1 if composition else 0
    if steps <= 0 or getattr(cfg, "attention_free", False):
        return 0
    blk = ring_block_bytes(cfg, tokens_per_rank, tp=tp,
                           kv_sharded=kv_sharded)
    return attn_layer_count(cfg) * steps * ring_edges(composition) * blk


def pp_tick_bytes(cfg, num_stages: int, tokens_global: int,
                  pos_width: int = 1) -> int:
    """Fleet bytes of one wavefront tick's stage roll: every stage sends
    its [T, d_model] activation slice plus seg/pos metadata to its
    neighbour (`parallel.pipeline.pipeline_hidden`'s ``jnp.roll``)."""
    per_stage = tokens_global * (cfg.d_model * act_itemsize(cfg)
                                 + 4 + 4 * pos_width)
    return num_stages * per_stage


def offload_dispatch_bytes(cfg, offload_ratio: float, tokens_global: int,
                           num_stages: int = 1) -> Tuple[float, float]:
    """Predicted (d2h, h2d) bytes of one dispatch at the *continuous*
    Eq. 3 ratio: r x stage-local periods x residual-stream bytes per
    period.  Execution quantizes the window to whole periods
    (`core.offload.offload_periods`), so |predicted - measured| is the
    genuine ratio->period quantization error."""
    if offload_ratio <= 0:
        return 0.0, 0.0
    n = OF.scan_periods(cfg)
    if num_stages > 1:
        n //= num_stages
    resid = tokens_global * cfg.d_model * act_itemsize(cfg)
    moved = float(offload_ratio) * n * resid
    if num_stages > 1:
        moved *= num_stages                       # every stage's window
    return moved, moved


def predicted_hbm_bytes(cfg, coeffs: OF.CostCoeffs, tokens_per_rank: int,
                        offload_ratio: float, hdp: int,
                        num_stages: int = 1) -> int:
    """Coarse per-rank peak-HBM watermark: bf16 params + fp32 grad
    accumulators + ZeRO-1-sharded optimizer state (12 B/param over hdp) +
    the activation footprint of `tokens_per_rank` at the wave's Eq. 3
    offload discount (only the first/last layers stay fully resident at
    r = 1 — the D(s) numerator of core/offload.py)."""
    p = cfg.param_count()
    ell = max(cfg.num_layers, 3)
    params_b = p * act_itemsize(cfg)
    grads_b = 4 * p
    opt_b = 12.0 * p / max(hdp, 1)
    discount = 1.0 - offload_ratio * (ell - 2) / ell
    act_b = OF.act_bytes(coeffs, tokens_per_rank) * ell * discount
    if num_stages > 1:
        act_b /= num_stages
    return int(params_b + grads_b + opt_b + act_b)


# ---------------------------------------------------------------------------
# plan-level pricing (benchmarks: no mesh, no tensors)
# ---------------------------------------------------------------------------

def plan_comm_bytes(plan, cfg, *, tp: int = 1) -> Dict[str, float]:
    """Price a `StepPlan`'s total forward ring traffic from the plan
    alone (benchmarks/comm_bench.py: HDP vs static-CP on one batch).
    Offload transfer bytes are priced at each wave's planned ratio."""
    ring = 0.0
    d2h = 0.0
    hdp = len(plan.waves[0].costs) if plan.waves else 1
    for w in plan.waves:
        tokens_per_rank = w.c_mult * plan.capacity
        ring += wave_ring_bytes(cfg, w.composition, tokens_per_rank, tp=tp)
        d2h += offload_dispatch_bytes(cfg, w.offload_ratio,
                                      hdp * tokens_per_rank)[0]
    return {"ring": ring, "offload_d2h": d2h, "offload_h2d": d2h,
            "total": ring + 2 * d2h}


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def _rel_residual(pred: float, meas: float) -> float:
    return abs(pred - meas) / max(abs(pred), abs(meas), 1.0)


def new_totals() -> Dict:
    """Empty aggregate (also the controller's fleet-ledger shape)."""
    return {"n": 0,
            "pred": {k: 0.0 for k in COMM_KINDS},
            "meas": {k: 0.0 for k in COMM_KINDS},
            "hbm_pred_peak": 0.0, "hbm_meas_peak": 0.0}


def merge_record(totals: Dict, rec: Dict) -> Dict:
    """Fold one ledger record (local or off the telemetry wire) into an
    aggregate from `new_totals` — the controller's fleet accumulator."""
    totals["n"] += 1
    for k in COMM_KINDS:
        totals["pred"][k] += float(rec.get("pred", {}).get(k, 0.0))
        totals["meas"][k] += float(rec.get("meas", {}).get(k, 0.0))
    if rec.get("hbm_pred"):
        totals["hbm_pred_peak"] = max(totals["hbm_pred_peak"],
                                      float(rec["hbm_pred"]))
    if rec.get("hbm_meas"):
        totals["hbm_meas_peak"] = max(totals["hbm_meas_peak"],
                                      float(rec["hbm_meas"]))
    return totals


def totals_summary(totals: Dict) -> Dict:
    """Residual view of an aggregate: per-kind relative residual plus the
    combined comm residual (the CI gate quantity)."""
    pred, meas = totals["pred"], totals["meas"]
    residual = {k: _rel_residual(pred[k], meas[k])
                for k in COMM_KINDS if pred[k] or meas[k]}
    p_tot = sum(pred.values())
    m_tot = sum(meas.values())
    return {"n": totals["n"],
            "pred_total": p_tot, "meas_total": m_tot,
            "residual": residual,
            "comm_residual": _rel_residual(p_tot, m_tot)
            if (p_tot or m_tot) else 0.0,
            "hbm_pred_peak": totals["hbm_pred_peak"],
            "hbm_meas_peak": totals["hbm_meas_peak"]}


class Ledger:
    """Per-process predicted/measured ledger the trainer feeds once per
    dispatch.  Bounded memory: raw records keep the most recent
    ``max_records``; the running totals cover everything."""

    def __init__(self, cfg, *, capacity: int, hdp: int,
                 num_stages: int = 1, tp: int = 1,
                 coeffs: Optional[OF.CostCoeffs] = None,
                 offload_active: bool = False,
                 kv_sharded: Optional[bool] = None,
                 pos_width: int = 1, max_records: int = 4096):
        self.cfg = cfg
        self.capacity = int(capacity)
        self.hdp = int(hdp)
        self.num_stages = int(num_stages)
        self.tp = int(tp)
        self.coeffs = coeffs if coeffs is not None else \
            OF.analytic_coeffs(cfg)
        self.offload_active = bool(offload_active)
        self.kv_sharded = kv_sharded
        self.pos_width = int(pos_width)
        self.records: deque = deque(maxlen=int(max_records))
        self.totals = new_totals()
        self.step_bytes: Dict[str, float] = {}   # zero1 analytic (per step)

    # -- predicted side ------------------------------------------------
    def predict_dispatch(self, composition: Sequence[int], c_mult: int,
                         offload_ratio: float, n_waves: int = 1) -> Dict:
        """Predicted fleet bytes of one dispatch: a single wave, or a
        pipelined round of ``n_waves`` microbatches (every tick of the
        M + S - 1 wavefront runs all stages' rings and one stage roll)."""
        tokens_per_rank = int(c_mult) * self.capacity
        tokens_global = self.hdp * tokens_per_rank
        s = self.num_stages
        ring1 = wave_ring_bytes(self.cfg, composition, tokens_per_rank,
                                tp=self.tp, kv_sharded=self.kv_sharded)
        pred = {k: 0.0 for k in COMM_KINDS}
        if s > 1:
            ticks = n_waves + s - 1
            pred["ring"] = float(ticks * ring1)
            pred["pp"] = float(ticks * pp_tick_bytes(
                self.cfg, s, tokens_global, self.pos_width))
            mult = ticks
        else:
            pred["ring"] = float(n_waves * ring1)
            mult = n_waves
        if self.offload_active and offload_ratio > 0:
            d2h, h2d = offload_dispatch_bytes(self.cfg, offload_ratio,
                                              tokens_global, s)
            pred["offload_d2h"] = d2h * mult
            pred["offload_h2d"] = h2d * mult
        return pred

    def predict_hbm(self, c_mult: int, offload_ratio: float) -> int:
        r = offload_ratio if self.offload_active else 0.0
        return predicted_hbm_bytes(self.cfg, self.coeffs,
                                   int(c_mult) * self.capacity, r,
                                   self.hdp, self.num_stages)

    # -- recording -----------------------------------------------------
    def record_dispatch(self, *, step: int, idx: int, kind: str,
                        composition: Sequence[int], c_mult: int,
                        offload_ratio: float, n_waves: int = 1,
                        fresh: bool = False,
                        measured: Optional[Dict] = None,
                        hbm_peak: Optional[float] = None) -> Dict:
        """Build, aggregate, and return one dispatch record.  ``measured``
        is the trace-time tally (cached per executable); ``hbm_peak`` the
        sampled device watermark (None on backends without memory_stats)."""
        pred = self.predict_dispatch(composition, c_mult, offload_ratio,
                                     n_waves)
        meas = {k: float(measured.get(k, 0.0)) for k in COMM_KINDS} \
            if measured is not None else None
        rec = {"step": int(step), "idx": int(idx), "kind": str(kind),
               "comp": list(int(g) for g in composition),
               "c_mult": int(c_mult), "n_waves": int(n_waves),
               "fresh": bool(fresh), "pred": pred,
               "hbm_pred": self.predict_hbm(c_mult, offload_ratio)}
        if meas is not None:
            rec["meas"] = meas
        if hbm_peak is not None:
            rec["hbm_meas"] = float(hbm_peak)
        self.records.append(rec)
        merge_record(self.totals, rec)
        return rec

    def set_step_bytes(self, bytes_by_kind: Dict[str, float]) -> None:
        """Attach per-optimizer-step analytic collectives (ZeRO-1 grad
        reduce + param all-gather — `parallel.zero1.zero1_bytes`)."""
        self.step_bytes = dict(bytes_by_kind)

    # -- consumer view -------------------------------------------------
    def comm_residual(self) -> float:
        return totals_summary(self.totals)["comm_residual"]

    def summary(self) -> Dict:
        out = totals_summary(self.totals)
        if self.step_bytes:
            out["step_bytes"] = dict(self.step_bytes)
        return out

    def recent(self, n: int = 64) -> List[Dict]:
        return list(self.records)[-n:]
