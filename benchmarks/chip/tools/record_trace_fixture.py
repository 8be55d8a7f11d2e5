"""Record the small trace that ``tests/test_chipbench_trace.py`` reduces:
a jitted matmul, a Pallas kernel (the fused decode-tail attention at a
small size) and host spans, inside a ``bench.window`` span, on the chip.

    python3 benchmarks/chip/tools/record_trace_fixture.py <out_dir>
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ops import fused_decode_attention

    out = Path(sys.argv[1])
    B, H, KH, D, P, NP, K = 4, 8, 2, 128, 128, 17, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, D), bf)
    kp = jax.random.normal(ks[1], (NP, KH, P, D), bf)
    vp = jax.random.normal(ks[2], (NP, KH, P, D), bf)
    tables = jnp.arange(1, 17, dtype=jnp.int32).reshape(B, 4)
    lens = jnp.array([100, 300, 500, 17], jnp.int32)
    kt = jax.random.normal(ks[3], (B, KH, K, D), bf)
    vt = jax.random.normal(ks[4], (B, KH, K, D), bf)
    tl = jnp.array([1, 2, 3, 4], jnp.int32)
    x = jax.random.normal(ks[5], (2048, 2048), bf)
    mm = jax.jit(lambda a: a @ a)
    att = jax.jit(fused_decode_attention)
    mm(x).block_until_ready()
    att(q, kp, vp, tables, lens, kt, vt, tl).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.decode"):
                att(q, kp, vp, tables, lens, kt, vt, tl).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                mm(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out / 'small.xplane.pb'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
