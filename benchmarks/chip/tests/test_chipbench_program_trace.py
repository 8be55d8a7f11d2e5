"""``program_trace`` on traces whose numbers are known: one written here
event by event with the program's scopes and spans, the one recorded on a
TPU v5e (``fixtures/small.xplane.pb``), and a traced run of a CPU cell
through ``tools/engine_trace.py``."""
from pathlib import Path

import numpy as np
import pytest

import cells
import harness
import program_trace as PT
import trace_reduce as TR

FIX = Path(__file__).resolve().parent / "fixtures"
SAMPLE = "jit(fused_decode)/while/body/sample/sort:"
ATTN = "jit(fused_decode)/while/body/decode_attention/pallas_call:"

# (op, start ns, end ns, scope); the while loop encloses its body's ops
OPS = [("while.1", 100, 900, "jit(fused_decode)/while:"),
       ("sort.1", 100, 300, SAMPLE), ("_fused_decode_grouped", 300, 600,
                                      ATTN),
       ("sort.1", 600, 700, SAMPLE), ("fusion.9", 950, 1100,
                                      "jit(prefill)/dot_general:")]
MODULES = [("jit_fused_decode(1)", 100, 900), ("jit_prefill(2)", 950, 1100)]
SPANS = [("bench.window", 0, 1000, {}), ("bench.decode", 40, 960, {}),
         ("engine.decode", 45, 950, {}),
         ("engine.decode.prep", 45, 90, {}),
         ("engine.decode.wait", 90, 930, {}),
         ("engine.decode.unpack", 930, 950, {}),
         ("engine.admit", 960, 990, {"request_id": "r3"})]


def _plane(pid, name, lines, scopes=None):
    """Text proto of one plane; ``scopes`` puts a tf_op stat (stat
    metadata 98) in each named event's metadata."""
    meta, body = {}, []
    for i, (lname, rows) in enumerate(lines):
        evs = []
        for row in rows:
            ev, s, e = row[:3]
            mid = meta.setdefault(ev, len(meta) + 1)
            stat = "".join(f' stats {{ metadata_id: 97 str_value: "{v}" }}'
                           for v in (row[3].values() if len(row) > 3
                                     and isinstance(row[3], dict) else ()))
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {s * 1000} "
                       f"duration_ps: {(e - s) * 1000}{stat} }}")
        body.append(f'lines {{ id: {i + 1} name: "{lname}" timestamp_ns: 0 '
                    f'{" ".join(evs)} }}')
    md = []
    for k, v in meta.items():
        st = f' stats {{ metadata_id: 98 str_value: "{scopes[k]}" }}' \
            if scopes and k in scopes else ""
        md.append(f'event_metadata {{ key: {v} value {{ id: {v} name: '
                  f'"{k}"{st} }} }}')
    sm = ('stat_metadata { key: 98 value { id: 98 name: "tf_op" } } '
          'stat_metadata { key: 97 value { id: 97 name: "request_id" } }')
    return f'planes {{ id: {pid} name: "{name}" {" ".join(body)} ' \
        f'{" ".join(md)} {sm} }}'


def _write(path, scopes):
    from jax.profiler import ProfileData
    txt = _plane(1, "/host:CPU", [("python", SPANS)]) + _plane(
        2, "/device:TPU:0", [("XLA Ops", [o[:3] for o in OPS]),
                             ("XLA Modules", MODULES)], scopes)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return path


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    path = _write(tmp_path_factory.mktemp("trace") / "t.xplane.pb",
                  {o[0]: o[3] for o in OPS})
    tr = TR.reduce_trace(path)
    return tr, PT.read(path, tr.window)


def test_scopes_are_read_from_the_event_metadata(synthetic):
    tr, pt = synthetic
    assert pt.window == (0, 1000) and pt.conflicts == 0
    # the loop is left out; the prefill op is clipped to the window
    assert sorted(sc for sc, _, _ in pt.ops) == sorted(
        [SAMPLE, ATTN, SAMPLE, "jit(prefill)/dot_general:"])
    assert PT.scope_seconds(pt, PT.SAMPLE_IN_DECODE) == pytest.approx(300e-9)
    assert PT.scope_seconds(pt, "/decode_attention/") == pytest.approx(
        TR.op_seconds(tr, "^_fused_decode"))
    assert PT.scope_seconds(pt, r"^jit\(prefill\)") == pytest.approx(50e-9)


def test_one_name_with_two_scopes_is_counted(tmp_path):
    scopes, conflicts = PT.op_scopes(_write(tmp_path / "t.xplane.pb", {}))
    assert scopes == {} and conflicts == 0
    from jax.profiler import ProfileData

    def meta(i, scope):
        return (f'event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"sort.1" stats {{ metadata_id: 98 str_value: "{scope}" }} '
                f'}} }}')
    two = ('planes { id: 2 name: "/device:TPU:0" ' + meta(1, SAMPLE) + " "
           + meta(2, "jit(prefill)/sort:") + ' stat_metadata { key: 98 '
           'value { id: 98 name: "tf_op" } } }')
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(two))
    scopes, conflicts = PT.op_scopes(path)
    # one of the two is kept, whichever the file holds first
    assert conflicts == 1 and scopes["sort.1"] in (SAMPLE,
                                                   "jit(prefill)/sort:")


def test_engine_spans_and_their_readings(synthetic):
    tr, pt = synthetic
    names = [sp[0] for sp in pt.spans]
    assert "bench.window" not in names and len(names) == 5
    assert [sp[3] for sp in pt.spans if sp[0] == "engine.admit"] == \
        [{"request_id": "r3"}]
    # (45 ns prep + 20 ns unpack) over one decode span
    assert PT.decode_host_ms(pt) == pytest.approx(65e-6)
    # the wait ends 30 ns after the fused decode program's device end
    assert PT.clock_offset_ms(pt, tr.modules) == pytest.approx([30e-6])
    # gaps [0, 100) and [900, 950): their middles lie in the decode's prep
    # and wait, inside the harness's decode span
    gaps = PT.idle_gaps(tr, pt)
    assert [g[0] for g in gaps] == ["engine.decode.prep",
                                    "engine.decode.wait"]
    assert [g[0] for g in TR.idle_gaps(tr)] == ["decode", "decode"]


def test_decode_sample_ms_is_per_step_in_the_trace(synthetic):
    _, pt = synthetic
    rec = harness.Records(cell=None, dims=None, page=16, chips=1,
                          peaks=None, seconds=1.0)
    rec.trace_host = (10.0, 20.0)
    # 8 steps inside, 4 of 8 steps half inside, 1 step outside
    rec.decode_calls = [(11.0, 12.0, 8, 8, []), (19.0, 21.0, 8, 8, []),
                        (21.0, 22.0, 1, 1, [])]
    assert PT.decode_sample_ms(pt, rec) == pytest.approx(300e-6 / 12)
    rec.decode_calls = []
    assert PT.decode_sample_ms(pt, rec) is None


def test_clamp_shares_are_deltas_over_the_window():
    opened = {"decode_syncs": 10, "k1_prefill": 2, "k1_batch": 1,
              "k_pool": 0}
    closed = {"decode_syncs": 30, "k1_prefill": 7, "k1_batch": 4,
              "k_pool": 1}
    assert PT.clamp_shares(opened, closed) == pytest.approx(
        {"k1_prefill": 25.0, "k1_batch": 15.0, "k_pool": 5.0})
    assert PT.clamp_shares(opened, opened) == {}


def test_recorded_tpu_trace_scopes():
    path = FIX / "small.xplane.pb"
    tr = TR.reduce_trace(path)
    pt = PT.read(path, tr.window)
    t = PT.scope_seconds(pt, "pallas_call")
    assert t > 0 and t == pytest.approx(TR.op_seconds(tr, "^_fused_decode"))
    assert PT.scope_seconds(pt, r"^jit\(fused_decode_attention\)/") \
        < TR.op_seconds(tr, ".")
    assert pt.spans == []          # recorded before the engine had spans


def test_a_traced_cpu_run_reports_the_engine_trace(tmp_path, monkeypatch):
    from tools import engine_trace
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    root = cells.make_root(tmp_path)
    res = engine_trace.traced_run(root, "tiny-chat", 8, 2.0,
                                  require_chip=False)
    assert res["correct"], res["check"]
    assert list(res)[-2:] == ["engine_trace", "check"]
    et = res["engine_trace"]
    # the CPU trace has host spans and no device plane
    assert {"engine.step", "engine.decode", "engine.decode.prep",
            "engine.decode.wait", "engine.decode.unpack"} <= set(et["spans"])
    assert et["decode_host_ms"] > 0 and et["decode_sample_ms"] is None
    assert et["clamps"]["decode_syncs"] > 0
    calls = et["clamps"]["decode_syncs"]
    k1 = res["metrics"]["decode_k1_share.chat"]["value"]
    # the counters split what the harness counts from outside, within the
    # one call by which the window's edges can differ
    assert sum(et["clamp_share"].values()) == pytest.approx(
        k1, abs=100.0 / calls + 1e-9)
    assert np.isfinite(et["reduce_s"]) and np.isfinite(et["read_s"])
