"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels.flash_attention.ops import (flash_attention,
                                               paged_flash_prefill)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import (fused_decode_attention,
                                               fused_decode_attention_sharded,
                                               paged_attention,
                                               paged_attention_sharded)
from repro.kernels.paged_attention.ref import (fused_decode_attention_ref,
                                               paged_attention_ref,
                                               paged_prefill_attention_ref)
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_chunked, ssd_decode_step


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, S, H, KH, D, causal, window
    (2, 256, 8, 2, 64, True, 0),
    (1, 512, 4, 4, 128, True, 0),
    (2, 384, 8, 1, 64, False, 0),     # MQA, bidirectional (encoder)
    (1, 512, 8, 2, 64, True, 128),    # sliding window
    (2, 100, 4, 2, 32, True, 0),      # non-block-multiple seq
    (1, 128, 56, 8, 128, True, 0),    # yi/llava head config
    (1, 160, 20, 20, 64, True, 0),    # qwen MHA head config
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    B, S, H, KH, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=128, k_block=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    assert out.dtype == dtype
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


def test_flash_attention_block_shape_invariance():
    """Output must not depend on the tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 512, 8, 64))
    k = jax.random.normal(ks[1], (2, 512, 4, 64))
    v = jax.random.normal(ks[2], (2, 512, 4, 64))
    outs = [flash_attention(q, k, v, q_block=qb, k_block=kb, interpret=True)
            for qb, kb in [(64, 64), (128, 256), (256, 128), (512, 512)]]
    for o in outs[1:]:
        assert_allclose(np.asarray(outs[0]), np.asarray(o), rtol=1e-5,
                        atol=1e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # B, H, KH, D, page, PPS, NP
    (4, 8, 2, 64, 128, 4, 32),
    (2, 4, 4, 128, 128, 8, 64),
    (3, 8, 1, 64, 256, 2, 16),        # MQA
    (2, 56, 8, 128, 128, 4, 16),      # yi head config (G=7, sublane-padded)
    (2, 12, 4, 64, 128, 4, 16),       # GQA G=3 (pads to the sublane tile)
    (1, 32, 2, 64, 128, 2, 8),        # GQA G=16 (exceeds one f32 sublane)
    (2, 40, 8, 32, 128, 3, 16),       # GQA G=5, small head dim
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(case, dtype):
    B, H, KH, D, page, PPS, NP = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (NP, KH, page, D), dtype)
    vp = jax.random.normal(ks[2], (NP, KH, page, D), dtype)
    tables = jax.random.randint(ks[3], (B, PPS), 0, NP)
    lens = jax.random.randint(ks[4], (B,), 1, PPS * page + 1)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


def test_paged_attention_gqa_group_padding_is_invisible():
    """The GQA wrapper pads the query-group axis to the sublane tile; the
    padded rows must not leak: each KV head's G query heads must produce
    exactly what an unpadded per-head gather computes."""
    B, H, KH, D, page, PPS, NP = 2, 6, 2, 64, 128, 3, 8   # G=3 -> pads to 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    tables = jax.random.randint(ks[3], (B, PPS), 0, NP)
    lens = jax.random.randint(ks[4], (B,), 1, PPS * page + 1)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    assert out.shape == (B, H, D)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_attention_rejects_ragged_grouping():
    with pytest.raises(AssertionError, match="multiple of kv heads"):
        paged_attention(jnp.zeros((1, 6, 64)), jnp.zeros((4, 4, 128, 64)),
                        jnp.zeros((4, 4, 128, 64)),
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.ones((1,), jnp.int32), interpret=True)


def test_paged_attention_page_permutation_invariance():
    """Physically permuting pages (and the table with them) must not change
    the result — the indirection property PagedAttention relies on."""
    B, H, KH, D, page, PPS, NP = 2, 8, 2, 64, 128, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    tables = jax.random.randint(ks[3], (B, PPS), 0, NP)
    lens = jnp.array([page * PPS, page * 2 + 17])
    out1 = paged_attention(q, kp, vp, tables, lens, interpret=True)
    perm = jax.random.permutation(ks[4], NP)
    inv = jnp.argsort(perm)
    out2 = paged_attention(q, kp[inv], vp[inv], perm[tables], lens,
                           interpret=True)
    assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6, atol=1e-6)


# edge geometry: page-boundary lengths, single-page, one-token, empty
# context — the cases the serving allocator actually produces
EDGE_LEN_CASES = [
    # page, PPS, lens (None entries filled below)
    (16, 4, [16, 32]),                # context_len % page_size == 0
    (16, 4, [64, 48]),                # full table, and 3 exact pages
    (16, 1, [7, 16]),                 # single-page table, partial + full
    (16, 4, [1, 17]),                 # one token; first token of page 2
]


@pytest.mark.parametrize("case", EDGE_LEN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_edge_lengths(case, dtype):
    page, PPS, lens = case
    B, H, KH, D, NP = len(lens), 6, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (NP, KH, page, D), dtype)
    vp = jax.random.normal(ks[2], (NP, KH, page, D), dtype)
    tables = jnp.arange(B * PPS, dtype=jnp.int32).reshape(B, PPS) % NP
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


def test_paged_attention_empty_context_is_finite():
    """A zero-length row has no valid positions: the kernel's normalizer
    clamp must yield finite output (zeros), never NaN, and live rows in
    the same batch must be unaffected. (The jnp reference softmaxes the
    all-masked row to uniform instead — the two paths only have to agree
    on rows that can actually be sampled from.)"""
    B, H, KH, D, page, PPS, NP = 2, 4, 2, 32, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    tables = jnp.arange(B * PPS, dtype=jnp.int32).reshape(B, PPS)
    lens = jnp.asarray([0, 20], jnp.int32)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    assert_allclose(np.asarray(out[1]), np.asarray(ref[1]), rtol=2e-5,
                    atol=2e-5)


# ---------------------------------------------------------------------------
# fused decode-tail attention
# ---------------------------------------------------------------------------

FUSED_CASES = [
    # B, H, KH, D, page, PPS, NP, Kt
    (3, 8, 2, 64, 16, 4, 16, 4),
    (2, 56, 8, 32, 16, 4, 16, 16),    # yi grouping G=7 (sublane-padded)
    (2, 4, 4, 32, 16, 2, 8, 1),       # MHA, K=1 tail
    (2, 4, 1, 32, 16, 2, 8, 5),       # MQA, odd tail (pads to sublane)
]


@pytest.mark.parametrize("case", FUSED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_decode_attention(case, dtype):
    B, H, KH, D, page, PPS, NP, Kt = case
    ks = jax.random.split(jax.random.PRNGKey(21), 6)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (NP, KH, page, D), dtype)
    vp = jax.random.normal(ks[2], (NP, KH, page, D), dtype)
    kt = jax.random.normal(ks[3], (B, KH, Kt, D), dtype)
    vt = jax.random.normal(ks[4], (B, KH, Kt, D), dtype)
    tables = jnp.arange(B * PPS, dtype=jnp.int32).reshape(B, PPS) % NP
    lens = jax.random.randint(ks[5], (B,), 0, PPS * page + 1)
    tail_lens = (jnp.arange(B, dtype=jnp.int32) * Kt // max(B - 1, 1)) \
        if B > 1 else jnp.full((B,), Kt, jnp.int32)
    tail_lens = jnp.maximum(tail_lens, 1)  # >= 1 like the fused loop
    out = fused_decode_attention(q, kp, vp, tables, lens, kt, vt,
                                 tail_lens, interpret=True)
    ref = fused_decode_attention_ref(q, kp, vp, tables, lens, kt, vt,
                                     tail_lens)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


def test_fused_decode_attention_equals_materialized_pages():
    """Committing the tail into the pages and running plain paged
    attention over context_len + tail_len must give the same answer — the
    deferred-commit contract of the fused decode loop."""
    B, H, KH, D, page, PPS, NP, Kt = 2, 8, 2, 64, 16, 4, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(22), 6)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    kt = jax.random.normal(ks[3], (B, KH, Kt, D))
    vt = jax.random.normal(ks[4], (B, KH, Kt, D))
    # disjoint tables so the committed tails can't collide across rows
    tables = jnp.arange(1, 1 + B * PPS, dtype=jnp.int32).reshape(B, PPS)
    lens = jnp.asarray([13, 32], jnp.int32)   # mid-page and page-boundary
    tail_lens = jnp.asarray([4, 3], jnp.int32)
    out = fused_decode_attention(q, kp, vp, tables, lens, kt, vt,
                                 tail_lens, interpret=True)
    kp2, vp2 = kp, vp
    for b in range(B):
        for j in range(int(tail_lens[b])):
            pos = int(lens[b]) + j
            pid = int(tables[b, pos // page])
            kp2 = kp2.at[pid, :, pos % page].set(kt[b, :, j])
            vp2 = vp2.at[pid, :, pos % page].set(vt[b, :, j])
    ref = paged_attention_ref(q, kp2, vp2, tables, lens + tail_lens)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged flash prefill
# ---------------------------------------------------------------------------

PREFILL_CASES = [
    # B, C, H, KH, D, page, PPS, NP, start
    (2, 16, 8, 2, 64, 16, 4, 16, 0),      # fresh prompt chunk
    (1, 16, 4, 4, 32, 16, 4, 8, 32),      # later chunk (cached prefix)
    (2, 8, 56, 8, 32, 16, 2, 8, 8),       # yi grouping, tiny chunk
    (1, 5, 4, 1, 32, 16, 1, 4, 0),        # MQA, ragged chunk, single page
    (1, 16, 4, 2, 32, 16, 4, 8, 15),      # chunk straddles a page boundary
]


@pytest.mark.parametrize("case", PREFILL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_prefill(case, dtype):
    B, C, H, KH, D, page, PPS, NP, start = case
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(ks[0], (B, C, H, D), dtype)
    kp = jax.random.normal(ks[1], (NP, KH, page, D), dtype)
    vp = jax.random.normal(ks[2], (NP, KH, page, D), dtype)
    tables = jnp.arange(B * PPS, dtype=jnp.int32).reshape(B, PPS) % NP
    kv_len = start + C
    assert kv_len <= PPS * page
    out = paged_flash_prefill(q, kp, vp, tables, start, kv_len,
                              interpret=True)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, start, kv_len)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


# ---------------------------------------------------------------------------
# shard_map variants (simulated mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh4():
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices; run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh(1, 4)


def test_paged_attention_sharded_matches_unsharded(mesh4):
    """shard_map over the kv-head axis (8 kv heads / 4 shards): per-shard
    kernels must reproduce the single-device kernel bit-for-bit — the
    heads are independent, no collective touches the math."""
    B, H, KH, D, page, PPS, NP = 2, 16, 8, 32, 16, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(41), 5)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    tables = jax.random.randint(ks[3], (B, PPS), 0, NP)
    lens = jax.random.randint(ks[4], (B,), 1, PPS * page + 1)
    ref = paged_attention(q, kp, vp, tables, lens, interpret=True)
    out = paged_attention_sharded(q, kp, vp, tables, lens, mesh=mesh4,
                                  interpret=True)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=0)


def test_fused_decode_attention_sharded_matches_unsharded(mesh4):
    B, H, KH, D, page, PPS, NP, Kt = 2, 8, 4, 32, 16, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(42), 6)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (NP, KH, page, D))
    vp = jax.random.normal(ks[2], (NP, KH, page, D))
    kt = jax.random.normal(ks[3], (B, KH, Kt, D))
    vt = jax.random.normal(ks[4], (B, KH, Kt, D))
    tables = jax.random.randint(ks[5], (B, PPS), 0, NP)
    lens = jnp.asarray([16, 9], jnp.int32)
    tail_lens = jnp.asarray([2, 4], jnp.int32)
    ref = fused_decode_attention(q, kp, vp, tables, lens, kt, vt,
                                 tail_lens, interpret=True)
    out = fused_decode_attention_sharded(q, kp, vp, tables, lens, kt, vt,
                                         tail_lens, mesh=mesh4,
                                         interpret=True)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

SSD_CASES = [
    # b, s, h, p, n, chunk
    (2, 256, 4, 64, 64, 64),
    (1, 512, 8, 32, 128, 128),
    (2, 200, 3, 16, 32, 64),          # non-chunk-multiple seq
    (1, 256, 24, 64, 128, 128),       # mamba2-130m layout
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd(case, dtype):
    b, s, h, p, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    a = (-jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1).astype(jnp.float32)
    B = jax.random.normal(ks[2], (b, s, n), dtype)
    C = jax.random.normal(ks[3], (b, s, n), dtype)
    y, st = ssd(x, a, B, C, chunk=chunk, interpret=True)
    yr, str_ = ssd_chunked(x, a, B, C, chunk)
    rt = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32), **rt)
    assert_allclose(np.asarray(st), np.asarray(str_), rtol=1e-4, atol=1e-4)


def test_ssd_matches_step_recurrence():
    """Chunked kernel == token-by-token recurrence (the SSD duality)."""
    b, s, h, p, n = 1, 96, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.2
    B = jax.random.normal(ks[2], (b, s, n))
    C = jax.random.normal(ks[3], (b, s, n))
    y, st = ssd(x, a, B, C, chunk=32, interpret=True)
    hstate = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        yt, hstate = ssd_decode_step(x[:, t], a[:, t], B[:, t], C[:, t], hstate)
        ys.append(yt)
    yr = jnp.stack(ys, axis=1)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3, atol=1e-3)
    assert_allclose(np.asarray(st), np.asarray(hstate), rtol=1e-3, atol=1e-3)
