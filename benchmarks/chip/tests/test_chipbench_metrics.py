"""Metric arithmetic on records made by hand: TPOT as (last frame - first
frame) / (tokens - 1), the decode shares and step time over the window,
and the shares from the trace and the cost functions, with a call that
straddles an edge of the trace counted by its share inside."""
import json
from pathlib import Path

import numpy as np
import pytest

import costs
import harness
import spec as spec_mod
import trace_reduce as TR

ROOT = Path(__file__).resolve().parents[3]


@pytest.fixture(scope="module")
def sp():
    return spec_mod.Spec(ROOT)


@pytest.fixture(scope="module")
def dims():
    cfg = json.loads((ROOT / "benchmarks/chip/configs/qwen1.5-4b.json")
                     .read_text())
    return spec_mod.Spec(ROOT).arch(cfg).Dims.of(cfg)


def records(dims):
    return harness.Records(cell=None, dims=dims, page=128, chips=1,
                           peaks={"bf16_flops": 100e12,
                                  "hbm_bytes_per_s": 1e12},
                           seconds=10.0, window=(100.0, 110.0),
                           drain_end=130.0)


def req(due, frames, n_tok, finished=True, prompt_len=10):
    r = harness.Req("r", due, prompt_len, n_tok)
    r.sent = due
    r.frames = frames
    r.tokens = [5] * sum(k for _, k in frames)
    r.finished = frames[-1][0] if finished and frames else None
    return r


def test_tpot_is_per_gap_between_first_and_last_frame(sp, dims):
    rec = records(dims)
    # first frame at 101.0 (1 token), then 8 tokens at 101.2: 8 gaps
    rec.requests = [req(100.5, [(101.0, 1), (101.2, 8)], 9)]
    rec.requests += [req(100.5, [(101.0, 1)], 1)]      # one token: left out
    assert sp.reader("tpot_p90_ms")(rec) == pytest.approx(200.0 / 8)


def test_decode_shares_and_step_time(sp, dims):
    rec = records(dims)
    rec.decode_calls = [(101.0, 101.1, 8, 1, [(10, 1)]),
                        (102.0, 102.4, 8, 8, [(10, 8)]),
                        (111.0, 111.4, 8, 8, [(10, 8)])]   # after the window
    assert sp.reader("decode_k1_share.chat")(rec) == pytest.approx(50.0)
    assert sp.reader("decode_step_ms.chat")(rec) == pytest.approx(500.0 / 9)


def trace_of(busy, window=(0.0, 1e9), ops=(), modules=()):
    return TR.Trace(window=window, chips=1,
                    busy_ns=float(sum(e - s for s, e in busy)),
                    ops=[(n, s, d) for n, s, d in ops],
                    modules=list(modules), spans=[],
                    busy=np.asarray(busy, float).reshape(-1, 2))


def test_idle_share_and_rooflines(sp, dims):
    rec = records(dims)
    rec.trace_host = (100.0, 101.0)
    # one decode call of 2 sequences; the kernel ran 1 ms in all
    rec.decode_calls = [(100.2, 100.3, 8, 8, [(300, 8), (1000, 3)])]
    rec.trace = trace_of([(0, 4e8)], ops=[
        ("_fused_decode_grouped.3", 1e8, 5e5),
        ("copy.4", 1.5e8, 9e5),
        ("_fused_decode_grouped.3", 2e8, 5e5),
        ("_paged_prefill_rows.7", 3e8, 9e5)])
    assert sp.reader("idle_share.chat")(rec) == pytest.approx(60.0)
    nbytes = costs.decode_attn_bytes(dims, 300, 8, 128) + \
        costs.decode_attn_bytes(dims, 1000, 3, 128)
    assert sp.reader("decode_attn_roofline.chat")(rec) == \
        pytest.approx(100.0 * nbytes / 1e12 / 1e-3)
    # mfu: the 11 decode tokens at their contexts, over 1 s x 100 TFLOP/s
    flops = sum(costs.token_flops(dims, 300 + j + 1) for j in range(8)) + \
        sum(costs.token_flops(dims, 1000 + j + 1) for j in range(3))
    assert sp.reader("mfu.chat")(rec) == pytest.approx(100.0 * flops / 1e14)


def test_readers_find_nothing_without_a_trace(sp, dims):
    rec = records(dims)
    for name in ("idle_share.chat", "mfu.chat", "decode_attn_roofline.chat",
                 "decode_k1_share.chat"):
        assert sp.reader(name)(rec) is None, name


def test_trace_share_of_a_call(dims):
    rec = records(dims)
    assert rec.trace_share(100.2, 100.3) == 0.0          # no trace
    rec.trace_host = (100.0, 101.0)
    assert rec.trace_share(100.2, 100.3) == 1.0
    assert rec.trace_share(99.5, 100.5) == pytest.approx(0.5)
    assert rec.trace_share(100.9, 101.3) == pytest.approx(0.25)
    assert rec.trace_share(98.0, 99.0) == 0.0
    assert rec.trace_share(99.0, 102.0) == pytest.approx(1 / 3)


def test_calls_straddling_the_trace_count_by_their_share(sp, dims):
    rec = records(dims)
    rec.trace_host = (100.0, 101.0)
    # a K=8 call whose last quarter falls in the trace, one wholly inside,
    # and one whose first half does; the kernel ran 1 ms inside the trace
    rec.decode_calls = [(99.4, 100.2, 8, 8, [(500, 8)]),
                        (100.3, 100.5, 8, 8, [(300, 8)]),
                        (100.8, 101.2, 8, 8, [(700, 8)])]
    rec.prefill_calls = [(99.9, 100.1, 0, 512), (100.6, 100.7, 512, 64)]
    rec.trace = trace_of([(0, 1e9)], ops=[
        ("_fused_decode_grouped.3", 1e8, 1e6)])
    nbytes = 0.25 * costs.decode_attn_bytes(dims, 500, 8, 128) + \
        costs.decode_attn_bytes(dims, 300, 8, 128) + \
        0.5 * costs.decode_attn_bytes(dims, 700, 8, 128)
    assert sp.reader("decode_attn_roofline.chat")(rec) == \
        pytest.approx(100.0 * nbytes / 1e12 / 1e-3)

    def tokens(ctx0):
        return sum(costs.token_flops(dims, ctx0 + j + 1) for j in range(8))
    flops = 0.25 * tokens(500) + tokens(300) + 0.5 * tokens(700) + \
        0.5 * costs.chunk_flops(dims, 0, 512) + \
        costs.chunk_flops(dims, 512, 64)
    assert sp.reader("mfu.chat")(rec) == pytest.approx(100.0 * flops / 1e14)


def test_costs_by_hand(dims):
    m = dims
    # one token at context 1: 2 x matmul weights + 4 L H D
    assert costs.token_flops(m, 1) == 2 * m.matmul_params() + \
        4 * m.L * m.H * m.D
    assert m.matmul_params() == m.L * (4 * 2560 * 2560 + 3 * 2560 * 6912) \
        + 2560 * 151936
    # a chunk's FLOPs are the sum of its tokens'
    assert costs.chunk_flops(m, 100, 3) == pytest.approx(
        sum(costs.token_flops(m, 100 + j + 1) for j in range(3)))
    # decode bytes: 3 pages of 128 positions for a context of 300
    page = 2 * 128 * 20 * 128 * 2
    one = costs.decode_attn_bytes(m, 300, 1, 128)
    assert one == m.L * (3 * page + 2 * 20 * 128 * 2 + 2 * 20 * 128 * 2)


# -- the queued job's readers ----------------------------------------------------

def test_output_tok_s_is_the_job_over_its_makespan(sp, dims):
    rec = records(dims)
    # the window opens at 100; the last final frame comes at 160, past the
    # window's close: the job's makespan is 60 s
    rec.requests = [req(100.0, [(101.0, 1), (130.0, 9)], 10),
                    req(100.0, [(102.0, 1), (160.0, 19)], 20)]
    assert sp.reader("output_tok_s")(rec) == pytest.approx(30 / 60.0)
    rec.requests.append(req(100.0, [(103.0, 1)], 5, finished=False))
    assert sp.reader("output_tok_s")(rec) is None
    rec.requests = []
    assert sp.reader("output_tok_s")(rec) is None


def test_prefix_hit_share_counts_every_admission(sp, dims):
    rec = records(dims)
    assert sp.reader("prefix_hit_share.docqa")(rec) is None
    # (time, prompt tokens, cached tokens); a restore counts again
    rec.prefills = [(100.5, 4000, 0), (101.0, 4050, 3968),
                    (101.5, 4020, 3968), (102.0, 1000, 0)]
    assert sp.reader("prefix_hit_share.docqa")(rec) == \
        pytest.approx(100.0 * 7936 / 13070)
    rec.prefills.append((99.0, 5000, 5000))      # before the window
    assert sp.reader("prefix_hit_share.docqa")(rec) == \
        pytest.approx(100.0 * 7936 / 13070)


def test_prefill_device_time_and_roofline_straddling_the_trace(sp, dims):
    rec = records(dims)
    rec.trace_host = (100.0, 101.0)
    # a chunk whose last quarter falls in the trace, one wholly inside,
    # and one whose first half does
    rec.prefill_calls = [(99.4, 100.2, 0, 512), (100.3, 100.5, 512, 512),
                         (100.8, 101.2, 4096, 64), (100.6, 100.6, 0, 0)]
    rec.trace = trace_of([(0, 1e9)], ops=[
        ("_paged_prefill_rows.3", 1e8, 2e6),
        ("_fused_decode_grouped.4", 3e8, 1e6),
        ("_paged_prefill_rows.3", 5e8, 3e6)], modules=[
        ("jit__chunk_prefill_impl", 1e8, 4e7),
        ("jit_fused_decode", 2e8, 3e7),
        ("jit__chunk_prefill_impl", 6e8, 2e7)])
    tokens = 0.25 * 512 + 512 + 0.5 * 64
    assert sp.reader("prefill_ms_per_ktok.docqa")(rec) == \
        pytest.approx(0.06 * 1e6 / tokens)

    def least(pos, n):
        return max(costs.prefill_attn_flops(dims, pos, n) / 100e12,
                   costs.prefill_attn_bytes(dims, pos, n, 128) / 1e12)
    want = 0.25 * least(0, 512) + least(512, 512) + 0.5 * least(4096, 64)
    assert sp.reader("prefill_attn_roofline.docqa")(rec) == \
        pytest.approx(100.0 * want / 5e-3)
    # the short chunk at a long context is bound by its bytes, the others
    # by their FLOPs
    assert costs.prefill_attn_bytes(dims, 4096, 64, 128) / 1e12 > \
        costs.prefill_attn_flops(dims, 4096, 64) / 100e12
    assert costs.prefill_attn_flops(dims, 512, 512) / 100e12 > \
        costs.prefill_attn_bytes(dims, 512, 512, 128) / 1e12


def test_prefill_readers_find_nothing_without_a_trace_or_kernel(sp, dims):
    rec = records(dims)
    rec.prefill_calls = [(100.3, 100.5, 0, 512)]
    for name in ("prefill_ms_per_ktok.docqa", "prefill_attn_roofline.docqa"):
        assert sp.reader(name)(rec) is None, name
    rec.trace_host = (100.0, 101.0)
    rec.trace = trace_of([(0, 1e9)], ops=[("fusion.1", 1e8, 1e6)])
    for name in ("prefill_ms_per_ktok.docqa", "prefill_attn_roofline.docqa"):
        assert sp.reader(name)(rec) is None, name


def test_prefill_costs_by_hand(dims):
    m = dims
    # a chunk of 3 tokens at position 100 sees 101, 102 and 103 keys
    assert costs.prefill_attn_flops(m, 100, 3) == \
        4 * m.L * m.H * m.D * (101 + 102 + 103)
    # its attention is the attention part of chunk_flops
    assert costs.chunk_flops(m, 100, 3) - \
        2 * m.matmul_params() * 3 == costs.prefill_attn_flops(m, 100, 3)
    # 103 positions: one page of K and V a layer, and q and out of 3 rows
    assert costs.prefill_attn_bytes(m, 100, 3, 128) == \
        m.L * (costs.kv_page_bytes(m, 128) + 2 * 3 * 20 * 128 * 2)
