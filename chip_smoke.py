"""Smoke test on the chip: full-width qwen1.5-4b served through the
engine's Pallas path.

    python chip_smoke.py [--seed N]        # one TPU chip
    python chip_smoke.py --chips 4         # only the 4-chip sharded path

One process holds the chip(s); nothing here starts another. On one chip:

1. print the devices and the JAX version;
2. compile each Pallas kernel of the serving path (paged decode, fused
   decode-tail, paged flash prefill) at qwen1.5-4b widths and compare it
   with its jnp reference;
3. build full-width qwen1.5-4b (40 layers, d_model 2560, 20 query / 20 kv
   heads, head_dim 128, vocab 151936, bf16) with random weights from
   ``--seed``, initialized on the device;
4. serve 8 greedy requests (prompts of 64-512 tokens, 32 output tokens
   each) through the typed /v1 path ``repro.launch.serve`` uses
   (``CompletionRequest`` -> ``to_inference_request``) on a
   ``ContinuousBatchingEngine`` with the paged backend, fused decode and 8
   decode steps per host sync: once cold (compiling), once warm;
5. check that every request finished with its token count, that no
   logits crossed to the host, that the compiled decode step holds a
   Pallas kernel (``tpu_custom_call``), and that every served token is the
   choice of a teacher-forced reference forward up to bf16 near-ties.

``--chips 4`` runs only the sharded path and what it is compared with:
the same model on a 1x4 (data, model) mesh with the kernels under
shard_map, serving the same requests, and the sharded engine's prefill
logits against the same forward on one of the chips.

The last line printed is one JSON object, ``{"ok": true, "device":
{...}}``. A failed check exits nonzero before it; so does a host without
a TPU, or a directory without the rest of the repository.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-4b"
SLOTS = 8
MAX_LEN = 1024
PAGE = 128                 # one (128, 128) bf16 tile per streamed block
DECODE_K = 8               # fused decode steps per host sync
GEN = 32                   # output tokens per request
PROMPT_LENS = (64, 100, 128, 200, 256, 320, 400, 512)
CHUNK = 256                # prefill chunk for the flash-prefill check

# Kernel vs reference. Both take bf16 in and accumulate in f32 (the
# reference at highest matmul precision) and round the output to bf16;
# outputs are softmax averages of N(0, 1) values, |o| < 1, where a bf16
# ulp is <= 2^-8. 2e-2 leaves room for the kernel's own matmul passes and
# online-softmax rescaling; a wrong page, head or mask moves outputs by
# O(0.1).
KERNEL_TOL = 2e-2
# Served tokens vs a teacher-forced dense forward. Logits leave the model
# rounded to bf16 (~0.02 at |logit| ~ 4) and the two paths differ in
# attention kernel, KV page layout and reduction order across 40 bf16
# layers, so near-ties may flip; a token the served path got wrong (a
# misrouted page or slot) sits several units below the reference max.
TOKEN_LOGIT_TOL = 0.5
# Sharded vs one-chip prefill logits: the 1x4 mesh reduces the wo / w2
# contractions as 4 bf16 partial sums, a different rounding order than
# one chip, compounded over 40 layers; a broken head split or gather
# gives O(1) differences.
SHARD_LOGIT_TOL = 0.25


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")
    log(f"ok: {msg}")


# -- kernels vs references ---------------------------------------------------

def kernel_parity(cfg, seed: int) -> None:
    """Each serving kernel, compiled at ``cfg``'s widths in its dtype,
    against its jnp reference on random pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import paged_flash_prefill
    from repro.kernels.paged_attention.ops import (fused_decode_attention,
                                                   paged_attention)
    from repro.kernels.paged_attention.ref import (
        fused_decode_attention_ref, paged_attention_ref,
        paged_prefill_attention_ref)

    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    pps = MAX_LEN // PAGE
    NP = SLOTS * pps + 1
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    kp = jax.random.normal(ks[0], (NP, KH, PAGE, D), dt)
    vp = jax.random.normal(ks[1], (NP, KH, PAGE, D), dt)
    q = jax.random.normal(ks[2], (SLOTS, H, D), dt)
    tables = jnp.asarray(1 + rng.permutation(NP - 1)[:SLOTS * pps]
                         .reshape(SLOTS, pps), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_LEN - DECODE_K + 1, SLOTS),
                       jnp.int32)
    kt = jax.random.normal(ks[3], (SLOTS, KH, DECODE_K, D), dt)
    vt = jax.random.normal(ks[4], (SLOTS, KH, DECODE_K, D), dt)
    tail_lens = jnp.asarray(rng.integers(1, DECODE_K + 1, SLOTS), jnp.int32)
    qc = jax.random.normal(ks[5], (1, CHUNK, H, D), dt)
    start, kv_len = MAX_LEN // 2 - CHUNK, MAX_LEN // 2

    cases = {
        "paged decode": (paged_attention, paged_attention_ref,
                         (q, kp, vp, tables, lens)),
        "fused decode-tail": (fused_decode_attention,
                              fused_decode_attention_ref,
                              (q, kp, vp, tables, lens, kt, vt, tail_lens)),
        "paged flash prefill": (paged_flash_prefill,
                                paged_prefill_attention_ref,
                                (qc, kp, vp, tables[:1], start, kv_len)),
    }
    for name, (kernel, ref, args) in cases.items():
        out = np.asarray(jax.jit(kernel)(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ref)(*args), np.float32)
        err = float(np.abs(out - want).max())
        check(np.isfinite(out).all() and out.shape == want.shape
              and np.allclose(out, want, atol=KERNEL_TOL, rtol=KERNEL_TOL),
              f"{name} kernel {out.shape} vs reference: max |err| "
              f"{err:.3e} (tol {KERNEL_TOL})")


# -- serving -----------------------------------------------------------------

def make_requests(cfg, seed: int, tag: str):
    """Typed /v1 completions, converted exactly as repro.launch.serve
    converts them."""
    import numpy as np

    from repro.api import to_inference_request
    from repro.api.schemas import CompletionRequest

    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(PROMPT_LENS):
        req = CompletionRequest(
            model=cfg.name,
            prompt_tokens=rng.integers(2, cfg.vocab_size, n).tolist(),
            request_id=f"{tag}{i}", max_tokens=GEN,
            temperature=0.0).validate()
        reqs.append(to_inference_request(req))
    return reqs


def build_engine(model, params, mesh=None):
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig

    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        max_slots=SLOTS, max_seq_len=MAX_LEN, backend="paged",
        page_size=PAGE, fused_decode=True, decode_steps_per_sync=DECODE_K,
        mesh=mesh))
    be = eng.backend
    check(be.use_kernel and be._fused_use_pallas and not be._needs_view,
          f"engine resolved use_kernel={be.use_kernel} from the platform: "
          f"fused decode runs the Pallas decode-tail kernel"
          + (" under shard_map" if mesh is not None else ""))
    return eng


def serve(eng, reqs):
    """Serve ``reqs`` to completion and check each one's token count;
    returns ({request_id: tokens}, seconds)."""
    t0 = time.perf_counter()
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    outs = eng.run_to_completion()
    dt = time.perf_counter() - t0
    got = {o.request_id: list(o.output_tokens) for o in outs}
    check(len(outs) == len(reqs) and all(
        len(got.get(r.request_id, ())) == r.sampling.max_tokens
        and o.finish_reason == "length" for r, o in zip(
            sorted(reqs, key=lambda r: r.request_id),
            sorted(outs, key=lambda o: o.request_id))),
        f"{len(outs)}/{len(reqs)} requests finished with "
        f"{sum(map(len, got.values()))} tokens ({GEN} each)")
    return got, dt


def check_decode_hlo(eng) -> None:
    """The compiled K-step fused decode program holds the Pallas kernel."""
    be = eng.backend
    check(DECODE_K in be._fused, f"the engine ran {DECODE_K}-step fused "
          f"decode calls (compiled K: {sorted(be._fused)})")
    hlo = be._fused[DECODE_K].lower(
        be.params, be.pools, be._ctx_view, be._dec_st,
        *be._dev_tables).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"compiled {DECODE_K}-step decode HLO contains tpu_custom_call "
          f"({hlo.count('tpu_custom_call')} occurrences)")


def check_tokens_vs_reference(model, params, reqs, got) -> None:
    """Teacher-forced check: one dense forward of prompt + served tokens;
    each served token must be the reference's argmax up to a near-tie."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = max(PROMPT_LENS) + GEN
    toks = np.zeros((len(reqs), S), np.int32)
    pos = np.zeros((len(reqs), GEN), np.int32)
    served = np.zeros((len(reqs), GEN), np.int32)
    for i, r in enumerate(reqs):
        p, o = r.prompt_tokens, got[r.request_id]
        toks[i, :len(p) + GEN - 1] = p + o[:-1]
        pos[i] = len(p) - 1 + np.arange(GEN)
        served[i] = o

    @jax.jit
    def ref_logits(params, toks, pos):
        def one(t, p):
            logits, _ = model.prefill(params, {"tokens": t[None]},
                                      last_index=p)
            return logits[0]                                # (GEN, V)
        return jax.vmap(one)(toks, pos)

    ref = np.asarray(ref_logits(params, jnp.asarray(toks), jnp.asarray(pos)))
    picked = np.take_along_axis(ref, served[..., None], -1)[..., 0]
    gap = ref.max(-1) - picked
    exact = float((ref.argmax(-1) == served).mean())
    check(np.isfinite(ref).all() and float(gap.max()) <= TOKEN_LOGIT_TOL,
          f"served tokens vs teacher-forced reference: argmax match "
          f"{exact:.3f}, max logit gap {float(gap.max()):.4f} "
          f"(tol {TOKEN_LOGIT_TOL})")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(args) -> None:
    import jax

    from repro.configs import get_config
    from repro.models import make_model
    from repro.serving import backends

    cfg = get_config(ARCH)
    kernel_parity(cfg, args.seed)

    model = make_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(args.seed)))
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"{ARCH}: {n / 1e9:.3f}B params ({cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype}) initialized on "
        f"{jax.devices()[0]} in {time.perf_counter() - t0:.1f}s")

    eng = build_engine(model, params)
    backends.reset_transfer_stats()
    reqs = make_requests(cfg, args.seed, "cold")
    cold, t_cold = serve(eng, reqs)
    warm, t_warm = serve(eng, make_requests(cfg, args.seed, "warm"))
    check(backends.TRANSFER_STATS["decode_logits_transfers"] == 0,
          "no decode logits crossed to the host")
    log(f"host wall clock: cold pass (compile + serve) {t_cold:.2f}s, "
        f"warm pass (serve) {t_warm:.2f}s, compile ~{t_cold - t_warm:.2f}s; "
        f"warm tokens identical to cold: "
        f"{all(warm['warm' + k[4:]] == v for k, v in cold.items())}")
    check_decode_hlo(eng)
    check_tokens_vs_reference(model, eng.backend.params, reqs, cold)
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


def four_chips(args) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.distributed.sharding import ServeSharding
    from repro.launch.mesh import make_local_mesh
    from repro.models import make_model
    from repro.serving import backends

    cfg = get_config(ARCH)
    model = make_model(cfg)
    mesh = make_local_mesh(1, 4)
    shardings = ServeSharding(mesh, cfg).param_shardings(model.param_shapes())
    params = jax.block_until_ready(jax.jit(
        model.init_params, out_shardings=shardings)(
            jax.random.PRNGKey(args.seed)))
    eng = build_engine(model, params, mesh)
    used = {}
    for x in jax.tree.leaves((eng.backend.params, eng.backend.pools)):
        for shard in x.addressable_shards:
            used[shard.device] = used.get(shard.device, 0) + shard.data.nbytes
    check(len(used) == 4 and max(used.values()) < 1.25 * min(used.values()),
          f"params and KV pools spread over the mesh: bytes per chip "
          f"{sorted(used.values())}")

    backends.reset_transfer_stats()
    reqs = make_requests(cfg, args.seed, "tp")
    _, t = serve(eng, reqs)
    check(backends.TRANSFER_STATS["decode_logits_transfers"] == 0,
          "no decode logits crossed to the host")
    log(f"host wall clock: sharded pass (compile + serve) {t:.2f}s")
    check_decode_hlo(eng)

    prompt = reqs[-1].prompt_tokens
    sharded = np.asarray(eng.backend.prefill("probe", prompt), np.float32)
    eng.backend.free("probe")
    dev = mesh.devices.flat[1]
    single = jax.device_put(eng.backend.params, dev)
    logits, _ = jax.jit(model.prefill)(
        single, {"tokens": jax.device_put(np.asarray([prompt], np.int32),
                                          dev)})
    ref = np.asarray(logits[0], np.float32)
    err = float(np.abs(sharded - ref).max())
    check(np.isfinite(sharded).all() and err <= SHARD_LOGIT_TOL,
          f"sharded prefill logits vs one chip ({dev}) at position "
          f"{len(prompt) - 1}: max |diff| {err:.4f} (tol "
          f"{SHARD_LOGIT_TOL}); same argmax: "
          f"{sharded.argmax() == ref.argmax()}")
    log(f"peak_bytes_in_use per chip: "
        f"{[peak_bytes(d) for d in mesh.devices.flat]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    log(f"jax {jax.__version__}; devices: {devices}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"[chip_smoke] FAILED: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"[chip_smoke] FAILED: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {len(devices)}")
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
