"""Kernel microbenchmarks: Pallas (interpret-mode on CPU — correctness
path; TPU timings require hardware) vs the jnp reference, plus the
zigzag-dist-attn balance check (Fig. 14) and the ring-attention sweep
(`--ring`): the fused ring-flash engine vs the jnp oracle ring across
compositions, with tokens/s snapshots to ``BENCH_kernels.json`` so the
repo's kernel-throughput trajectory is recorded in-tree.

Standalone usage (from the repo root)::

    PYTHONPATH=src python -m benchmarks.kernel_bench --ring

(self-re-execs with ``--xla_force_host_platform_device_count`` when the
host platform exposes a single device, so the ring sweep always runs on
real ring compositions).  Interpret-mode Pallas runs the kernel body in
Python — its wall time is a correctness artifact, not kernel speed; the
jnp rows are the meaningful CPU throughput baseline.
"""
import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.core.attention import attention_ref
from repro.data.packing import zigzag_chunks
from repro.kernels import ops, ref

SNAPSHOT_PATH = "BENCH_kernels.json"


def _tok_s(tokens: int, us: float) -> float:
    # `tokens` counts sequence tokens: in the kernel layout [G, Hg, T, D]
    # the leading dims are kv-group / head dims of the SAME T tokens, so
    # local (t) and ring (T = c·hdp) rows are directly comparable
    return tokens / (us / 1e6) if us > 0 else 0.0


def run():
    rows = []
    rng = np.random.RandomState(0)
    g, hg, t, s, d = 2, 2, 256, 256, 64
    q = jnp.array(rng.randn(g, hg, t, d), jnp.float32)
    k = jnp.array(rng.randn(g, s, d), jnp.float32)
    v = jnp.array(rng.randn(g, s, d), jnp.float32)
    seg = jnp.ones(t, jnp.int32)
    pos = jnp.arange(t)

    interpret = ops.interpret_on(q.sharding)
    fa = jax.jit(lambda *a: ops.flash_attention(*a, d ** -0.5, True, 0,
                                                0.0, 128, 128, interpret))
    us = timeit(lambda: jax.block_until_ready(
        fa(q, k, v, seg, seg, pos, pos)))
    rows.append(("kernel.flash_attention.pallas_interp", us,
                 f"shape=({g},{hg},{t},{d}) tok/s={_tok_s(t, us):.0f}"))
    fr = jax.jit(lambda q, k, v: attention_ref(
        q.transpose(2, 0, 1, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        seg, seg, pos, pos, scale=d ** -0.5, kv_chunk=128))
    us = timeit(lambda: jax.block_until_ready(fr(q, k, v)))
    rows.append(("kernel.flash_attention.jnp_ref", us,
                 f"oracle path tok/s={_tok_s(t, us):.0f}"))

    tt, vv = 256, 8192
    logits = jnp.array(rng.randn(tt, vv), jnp.bfloat16)
    labels = jnp.array(rng.randint(0, vv, tt), jnp.int32)
    ce = jax.jit(lambda lg, lb: ops.fused_softmax_xent(lg, lb, interpret))
    us = timeit(lambda: jax.block_until_ready(ce(logits, labels)))
    rows.append(("kernel.fused_ce.pallas_interp", us, f"T={tt} V={vv}"))
    cr = jax.jit(lambda lg, lb: ref.fused_ce_ref(lg, lb)[0])
    us = timeit(lambda: jax.block_until_ready(cr(logits, labels)))
    rows.append(("kernel.fused_ce.jnp_ref", us, "oracle path"))

    # Fig. 14: zigzag layout balances the causal-mask area per rank
    length, group = 65_536, 8
    t0 = time.perf_counter()
    areas = []
    for _, lo, hi in zigzag_chunks(length, group):
        area = sum(e * e - b * b for b, e in (lo, hi))   # ~mask area ∝ Σpos
        areas.append(area)
    us = (time.perf_counter() - t0) * 1e6
    imb = max(areas) / min(areas)
    rows.append(("fig14.zigzag_mask_balance", us,
                 f"area_max/min={imb:.3f} (sequential split would be "
                 f"{(2*group-1):.0f}x)"))
    return rows


# ---------------------------------------------------------------------------
# ring-attention sweep (fused ring-flash engine vs the jnp oracle ring)
# ---------------------------------------------------------------------------

def _ring_compositions(hdp: int):
    comps = [(1,) * hdp]
    g = 2
    while g <= hdp:
        if hdp % g == 0:
            comps.append((g,) * (hdp // g))
        g *= 2
    if hdp >= 4:                        # one mixed leftover
        comps.append((hdp // 2,) + (1,) * (hdp - hdp // 2))
    return comps


def ring_run(iters: int = 2):
    """Fwd wall time of the full ring per backend × composition.  Uses as
    many host devices as available (1 device degrades to the local g=1
    path — run via ``--ring`` for the real sweep)."""
    from repro import compat
    from repro.core.ring import ring_attention

    hdp = max(1, jax.device_count())
    mesh = compat.make_mesh((hdp, 1), ("data", "model"))
    compat.set_mesh(mesh)
    c, h, g, d = 64, 4, 2, 16
    t = c * hdp
    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(t, h, d), jnp.float32)
    k = jnp.array(rng.randn(t, g, d), jnp.float32)
    v = jnp.array(rng.randn(t, g, d), jnp.float32)
    seg = jnp.ones(t, jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)

    rows = []
    for comp in _ring_compositions(hdp):
        for impl in ("pallas", "jnp"):
            fn = jax.jit(lambda q, k, v, impl=impl, comp=comp: ring_attention(
                q, k, v, seg, seg, pos, pos, mesh=mesh, hdp_axes=("data",),
                model_axis="model", composition=comp, kv_sharded=True,
                scale=d ** -0.5, kv_chunk=c,
                attn_impl="pallas" if impl == "pallas" else "ref"))
            us = timeit(lambda fn=fn: jax.block_until_ready(fn(q, k, v)),
                        iters=iters, warmup=1)
            name = "pallas_interp" if impl == "pallas" else "jnp_ref"
            tag = f"g{max(comp)}" + ("" if len(set(comp)) == 1 else "_mixed")
            rows.append((f"kernel.ring_flash.{name}.{tag}", us,
                         f"comp={comp} T={t} tok/s={_tok_s(t, us):.0f}"))
    return rows


def snapshot(path: str = SNAPSHOT_PATH, *, ring: bool = True,
             iters: int = 2, rows=None) -> dict:
    """Kernel-throughput snapshot (tokens/s) for the perf trajectory:
    local flash attention + (optionally) the ring sweep.  Pass ``rows``
    to snapshot an already-measured sweep instead of re-benchmarking."""
    if rows is None:
        rows = run() + (ring_run(iters=iters) if ring else [])
    snap: dict = {"devices": jax.device_count(),
                  "note": "pallas rows are interpret-mode (correctness "
                          "path); jnp rows are the CPU baseline"}
    for name, us, derived in rows:
        if name.startswith(("kernel.flash_attention", "kernel.ring_flash")):
            snap[name] = {"us_per_call": round(us, 1), "derived": derived}
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    return snap


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ring", action="store_true",
                    help="include the multi-device ring sweep (re-execs "
                         "with forced host devices when needed)")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--out", default=SNAPSHOT_PATH)
    args = ap.parse_args()

    if args.ring and jax.device_count() < 2:
        # one re-exec on a CPU host platform with forced devices; the
        # sentinel stops a loop if the flag cannot take effect
        if os.environ.get("REPRO_KB_REEXEC"):
            sys.exit("kernel_bench --ring: could not obtain a multi-device "
                     "host platform (forced CPU devices had no effect)")
        flags = os.environ.get("XLA_FLAGS", "")
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "REPRO_KB_REEXEC": "1",
               "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count"
                            f"={args.devices}"}
        sys.exit(subprocess.call(
            [sys.executable, "-m", "benchmarks.kernel_bench"] + sys.argv[1:],
            env=env))

    rows = run()
    if args.ring:
        rows += ring_run(iters=args.iters)
        # only a --ring sweep may (over)write the perf-trajectory file:
        # a ring-less snapshot would silently drop the ring rows
        snapshot(args.out, ring=True, rows=rows)
        sys.stderr.write(f"[kernel_bench] snapshot -> {args.out}\n")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
