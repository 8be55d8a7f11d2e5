"""``trace_reduce`` on traces whose numbers are known: one written here
event by event, and one recorded on a TPU v5e (``fixtures/small.xplane.pb``,
made by ``tools/record_trace_fixture.py``)."""
from pathlib import Path

import pytest

import trace_reduce as TR

FIX = Path(__file__).resolve().parent / "fixtures"

# times in ns; the proto's offsets are in ps from each line's timestamp
OPS = [("fusion.1", 100, 300, "jit_a"), ("_decode_tail_kernel", 250, 400,
                                         "jit_a"),
       ("fusion.2", 600, 700, "jit_b"), ("fusion.1", 950, 1300, "jit_b")]
MODULES = [("jit_a(1)", 100, 400), ("jit_b(2)", 600, 1300)]
SPANS = [("bench.window", 0, 1000), ("bench.step", 0, 1000),
         ("bench.decode", 90, 420), ("bench.wait", 420, 640)]


def _events(rows, meta):
    out = []
    for row in rows:
        name, s, e = row[:3]
        mid = meta.setdefault(name, len(meta) + 1)
        stat = ""
        if len(row) > 3:
            stat = f' stats {{ metadata_id: 99 str_value: "{row[3]}" }}'
        out.append(f"events {{ metadata_id: {mid} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000}{stat} }}")
    return " ".join(out)


def _plane(pid, name, lines):
    meta, body = {}, []
    for i, (lname, rows) in enumerate(lines):
        body.append(f'lines {{ id: {i + 1} name: "{lname}" timestamp_ns: 0 '
                    f'{_events(rows, meta)} }}')
    md = " ".join(f'event_metadata {{ key: {v} value {{ id: {v} name: '
                  f'"{k}" }} }}' for k, v in meta.items())
    sm = 'stat_metadata { key: 99 value { id: 99 name: "hlo_module" } }'
    return f'planes {{ id: {pid} name: "{name}" {" ".join(body)} {md} {sm} }}'


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    txt = _plane(1, "/host:CPU", [("python", SPANS)]) + _plane(
        2, "/device:TPU:0", [("XLA Ops", OPS), ("XLA Modules", MODULES)])
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return TR.reduce_trace(path)


def test_busy_is_the_union_of_ops_in_the_window(synthetic):
    tr = synthetic
    assert tr.window == (0, 1000) and tr.chips == 1
    # [100, 400) + [600, 700) + [950, 1000) clipped to the window
    assert tr.busy_ns == pytest.approx(300 + 100 + 50)


def test_op_and_program_seconds(synthetic):
    tr = synthetic
    assert TR.op_seconds(tr, "decode_tail") == pytest.approx(150e-9)
    assert TR.op_seconds(tr, r"^fusion") == pytest.approx(
        (200 + 100 + 50) * 1e-9)
    assert TR.module_seconds(tr, "jit_b") == pytest.approx(400e-9)
    assert TR.top_ops(tr)[0] == ["fusion", pytest.approx(350e-9)]


def test_idle_gaps_are_named_by_the_host_span(synthetic):
    gaps = TR.idle_gaps(synthetic)
    # [0, 100) under step, [400, 600) under wait, [700, 950) under step
    assert [g[0] for g in gaps] == ["step", "wait", "step"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9, 100e-9])


def test_recorded_tpu_trace():
    path = FIX / "small.xplane.pb"
    tr = TR.reduce_trace(path)
    assert tr.chips == 1
    assert 0 < tr.busy_ns < tr.window_ns
    # three calls of the decode-tail kernel in the window; the device's
    # clock reads about 1 ms early against the host's, so the first call's
    # kernel lands before the window's host span opens
    assert sum(1 for op in tr.ops if op[0].startswith("_fused_decode")) == 2
    assert 0 < TR.op_seconds(tr, "^_fused_decode") < TR.op_seconds(tr, ".")
    assert TR.module_seconds(tr, "fused_decode_attention") > 0
    names = {g[0] for g in TR.idle_gaps(tr)}
    assert names <= {"decode", "prefill", "wait", "host_idle"}
    assert "wait" in names
    # the three 5 ms waits are the longest gaps, each at least 5 ms
    assert sorted(g[1] for g in TR.idle_gaps(tr))[-3] >= 5e-3
