"""§Roofline: achieved-vs-peak bandwidth for the serving attention ops,
plus the three derived roofline terms per (arch x shape x mesh) from the
dry-run records in results/dryrun/*.json (when present).

**Kernel bandwidth** (always runs, CI smoke included): times the four
attention ops on the decode/prefill hot path — ``paged_attention``,
``fused_decode_attention``, ``paged_flash_prefill``, ``flash_attention``
— against a memory-traffic model (KV pages touched + q + output) and
reports achieved bytes/s as a fraction of peak. Decode-shaped attention
is memory-bound, so this fraction IS the roofline headroom. On TPU the
compiled Pallas kernels run against the chip's HBM_BW; on non-TPU hosts
the jnp reference implementations run (interpret-mode Pallas would time
the interpreter, not the op — the references are what the engine executes
hot on CPU) against a peak *measured in-process* by a jitted streaming
baseline, so the fraction stays a same-host ratio (contended-CPU noise
convention). Writes ``results/benchmarks/roofline.json``.

**Dry-run terms** (full runs with results/dryrun/ populated):

  compute_s    = dot_flops / PEAK_FLOPS          (per-chip, post-SPMD HLO)
  memory_s     = (traffic - convert) / HBM_BW    (TPU-projected: CPU-backend
                                                  bf16->f32 convert copies
                                                  excluded, see hlo_analysis)
  collective_s = collective_bytes / LINK_BW      (per-chip ICI bytes)

All inputs are PER-CHIP: the dry-run parses the post-SPMD per-device module
and multiplies while-loop bodies by trip counts (XLA's cost_analysis counts
them once).  MODEL_FLOPS uses the 6ND/2ND convention (attention flops
excluded), so ratio > 1 means attention-heavy, < 1 means padding/remat/
redundant compute.
"""
from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import print_table
from repro.configs import REGISTRY, SHAPES
from repro.kernels.flash_attention.ops import (flash_attention,
                                               paged_flash_prefill)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import (fused_decode_attention,
                                               kernels_compiled,
                                               paged_attention)
from repro.kernels.paged_attention.ref import (fused_decode_attention_ref,
                                               paged_attention_ref,
                                               paged_prefill_attention_ref)

PEAK_FLOPS = 197e12      # bf16 / chip (TPU v5e)
HBM_BW = 819e9           # bytes/s / chip
LINK_BW = 50e9           # bytes/s / ICI link
HBM_PER_CHIP = 16 << 30  # v5e: 16 GiB

BW_OUT_PATH = os.path.join("results", "benchmarks", "roofline.json")


# ---------------------------------------------------------------- kernel BW
def _best_time(fn, *args, iters=5):
    """Best-of-N wall clock of a jitted call (compile + warm excluded).
    Best-of, not mean: on a shared host contention only ever adds time."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _measured_peak(iters):
    """Streaming peak of THIS host, measured in-process: a jitted x + 1.0
    over an array far larger than L2, 2 (read+write) x nbytes. Keeps the
    achieved/peak fraction a same-host ratio instead of comparing CPU
    wall clock against a TPU datasheet number."""
    n = 1 << 24                               # 64 MiB f32
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    return 2.0 * x.nbytes / _best_time(f, x, iters=iters)


def kernel_bandwidth(fast: bool = False, smoke: bool = False) -> dict:
    """Achieved-vs-peak bandwidth for the four serving attention ops."""
    on_tpu = kernels_compiled()
    reduced = fast or smoke
    iters = 3 if reduced else 5
    B, KH, G, D, page = 4, 4, 4, 64, 16
    H = KH * G
    pps = 16 if reduced else 64               # pages per sequence
    S = pps * page
    NP = B * pps                              # pool sized to touched pages
    key = jax.random.PRNGKey(0)
    kq, kk = jax.random.split(key)
    kp = jax.random.normal(kk, (NP, KH, page, D), jnp.float32)
    vp = kp * 0.5
    tables = jnp.arange(NP, dtype=jnp.int32).reshape(B, pps)
    lens = jnp.full((B,), S, jnp.int32)       # full: every page is read
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    Kt = 16
    k_tail = jax.random.normal(kq, (B, KH, Kt, D), jnp.float32)
    v_tail = k_tail * 0.5
    tail_lens = jnp.full((B,), Kt, jnp.int32)
    C = 64 if reduced else 128                # prefill chunk
    qc = jax.random.normal(kq, (B, C, H, D), jnp.float32)
    Sq = 256 if reduced else 512              # dense flash sequence
    qd = jax.random.normal(kq, (B, Sq, H, D), jnp.float32)
    kd = jax.random.normal(kk, (B, Sq, KH, D), jnp.float32)
    vd = kd * 0.5

    if on_tpu:
        impl, peak = "pallas", HBM_BW
        dec, fus = paged_attention, fused_decode_attention
        pre, fla = paged_flash_prefill, flash_attention
    else:
        impl, peak = "reference (XLA)", _measured_peak(iters)
        dec = jax.jit(paged_attention_ref)
        fus = jax.jit(fused_decode_attention_ref)
        pre = jax.jit(paged_prefill_attention_ref,
                      static_argnames=("q_offset", "kv_len"))
        fla = jax.jit(attention_ref)

    kv = kp.nbytes + vp.nbytes
    cases = [
        # (op, bytes model, timed call)
        ("paged_attention", kv + 2 * q.nbytes,
         lambda: dec(q, kp, vp, tables, lens)),
        ("fused_decode_attention",
         kv + 2 * q.nbytes + k_tail.nbytes + v_tail.nbytes,
         lambda: fus(q, kp, vp, tables, lens, k_tail, v_tail, tail_lens)),
        ("paged_flash_prefill", kv + 2 * qc.nbytes,
         lambda: pre(qc, kp, vp, tables, S - C, S)),
        ("flash_attention",
         qd.nbytes + kd.nbytes + vd.nbytes + qd.nbytes,
         lambda: fla(qd, kd, vd)),
    ]
    rows, recs = [], []
    for name, nbytes, call in cases:
        t = _best_time(call, iters=iters)
        bw = nbytes / t
        frac = bw / peak
        recs.append({"op": name, "bytes": nbytes, "time_s": t,
                     "achieved_bytes_per_s": bw, "frac_of_peak": frac})
        rows.append([name, f"{nbytes / 2**20:.1f}", f"{t * 1e3:.3f}",
                     f"{bw / 1e9:.2f}", f"{frac * 100:.1f}%"])
    print_table(
        f"§Roofline kernel bandwidth [{impl}] — B={B} KH={KH} G={G} D={D}, "
        f"ctx {S}, peak {peak / 1e9:.1f} GB/s "
        f"({'HBM datasheet' if on_tpu else 'measured stream'})",
        ["op", "MiB moved", "best ms", "GB/s", "of peak"],
        rows, widths=[24, 10, 9, 8, 8])
    out = {"device": jax.default_backend(), "impl": impl,
           "peak_bytes_per_s": peak, "ctx_len": S, "batch": B,
           "kv_heads": KH, "group": G, "head_dim": D, "cases": recs}
    path = BW_OUT_PATH.replace(".json", ".fast.json") if reduced \
        else BW_OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {path}")
    # sanity gate, not a perf gate: a broken op (NaN timing, zero bytes,
    # wildly super-peak "bandwidth" from a mis-sized traffic model) fails;
    # honest sub-peak fractions (compute-bound flash, interpreter-free
    # reference on a noisy CPU) pass and are simply reported
    for r in recs:
        lo, hi = 0.0, 100.0 * peak
        if not (lo < r["achieved_bytes_per_s"] < hi):
            raise SystemExit(
                f"roofline: {r['op']} achieved "
                f"{r['achieved_bytes_per_s']:.3g} B/s is outside sane "
                f"bounds (peak {peak:.3g})")
    return out


def model_flops_per_device(arch: str, shape_name: str, devices: int) -> float:
    cfg = REGISTRY[arch]
    sh = SHAPES[shape_name]
    n = cfg.num_active_params
    if sh.kind == "train":
        return 6.0 * n * sh.global_batch * sh.seq_len / devices
    if sh.kind == "prefill":
        return 2.0 * n * sh.global_batch * sh.seq_len / devices
    return 2.0 * n * sh.global_batch / devices          # decode: one token


def terms(rec: dict) -> dict:
    flops = rec.get("dot_flops", 0.0)
    traffic = rec.get("traffic_bytes", 0.0) - rec.get("convert_bytes", 0.0)
    coll = rec.get("collective_bytes", 0.0)
    compute_s = flops / PEAK_FLOPS
    memory_s = traffic / HBM_BW
    coll_s = coll / LINK_BW
    bound = max((compute_s, "compute"), (memory_s, "memory"),
                (coll_s, "collective"))[1]
    step_s = max(compute_s, memory_s, coll_s)
    mf = model_flops_per_device(rec["arch"], rec["shape"], rec["devices"])
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "bound": bound, "step_s": step_s,
        "model_flops": mf,
        "useful_ratio": mf / flops if flops else 0.0,
        # roofline fraction: useful model flops per second vs peak
        "roofline_frac": (mf / step_s) / PEAK_FLOPS if step_s else 0.0,
    }


def load(out_dir: str = "results/dryrun") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def main(fast: bool = False, smoke: bool = False,
         out_dir: str = "results/dryrun") -> dict:
    bw = kernel_bandwidth(fast=fast, smoke=smoke)
    derived = []
    if glob.glob(os.path.join(out_dir, "*.json")):
        derived = _table(out_dir, "baseline (paper-faithful)")
        if glob.glob("results/dryrun_opt/*.json"):
            _table("results/dryrun_opt", "optimized (EXPERIMENTS.md §Perf)")
    elif not smoke:
        print(f"\n(no dry-run records under {out_dir}/ — derived-terms "
              f"table skipped; run the launch dry-run to populate it)")
    return {"kernel_bandwidth": bw, "derived_terms": derived}


def _table(out_dir: str, label: str) -> list[dict]:
    recs = [r for r in load(out_dir) if r.get("ok")]
    fails = [r for r in load(out_dir) if not r.get("ok")]
    rows = []
    out = []
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        t = terms(r)
        hbm = (r.get("argument_size_in_bytes", 0)
               + r.get("temp_size_in_bytes", 0)
               + r.get("output_size_in_bytes", 0)
               - r.get("alias_size_in_bytes", 0))
        rows.append([
            r["arch"], r["shape"], r["mesh"],
            f"{t['compute_s']*1e3:.2f}", f"{t['memory_s']*1e3:.2f}",
            f"{t['collective_s']*1e3:.2f}", t["bound"],
            f"{t['useful_ratio']:.2f}", f"{t['roofline_frac']*100:.1f}%",
            f"{hbm/2**30:.1f}",
        ])
        out.append({**r, **t})
    print_table(
        f"§Roofline [{label}] — per (arch x shape x mesh), per-chip terms",
        ["arch", "shape", "mesh", "compute ms", "memory ms", "coll ms",
         "bound", "6ND/HLO", "roofline", "GiB/chip"],
        rows, widths=[21, 11, 6, 10, 9, 8, 10, 7, 8, 8])
    if fails:
        print(f"\nFAILED cells: "
              f"{[(r['arch'], r['shape'], r['mesh']) for r in fails]}")
    over = [r for r in out
            if (r.get("argument_size_in_bytes", 0)
                + r.get("temp_size_in_bytes", 0)
                - r.get("alias_size_in_bytes", 0)) > HBM_PER_CHIP]
    print(f"\n{len(out)} cells OK; {len(fails)} failed; "
          f"{len(over)} cells exceed 16 GiB/chip (flagged for FSDP/remat)")
    return out


if __name__ == "__main__":
    main()
