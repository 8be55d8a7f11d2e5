"""``BENCHMARK.json`` against the benchmark's contract: every cell,
configuration, traffic mix and metric resolves to its file; names, units
and the other fields keep to their forms."""
import json
import re
from pathlib import Path

import pytest

import spec as spec_mod

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden_size|intermediate_size|"
                   r"num_experts_per_tok)$|latent|state|projection|expan")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3" and len(BENCH["command"]) <= 32
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert (ROOT / p).is_dir()
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_lines():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert spec_mod.NAME_RE.fullmatch(n), n
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec_mod.UNIT_RE.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in [w["why"] for w in BENCH["workloads"]] + \
            [c["source"] for c in BENCH["configs"]] + \
            [m["layer"] for m in BENCH["per_layer"]]:
        assert LINE.fullmatch(text), text


def test_every_cell_resolves_to_its_files():
    sp = spec_mod.Spec(ROOT)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        cell = sp.cell(w["name"])
        assert sp.traffic_path(w["traffic"]).is_file()
        vocab = cell.config["vocab_size"]
        assert cell.kind.max_context(cell.traffic) < \
            cell.config["engine"]["max_seq_len"]
        assert cell.kind.generate(cell.traffic, 1, 2, vocab)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w
        assert cell.per_layer, w
        for m in cell.per_layer:
            assert m["moves"] in e2e, m
        for m in cell.end_to_end + cell.per_layer:
            assert sp.metric_path(m["name"]).is_file(), m
            assert callable(sp.reader(m["name"]))


def test_configs_resolve_and_list_their_cuts():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in data and not WIDTH.search(key), key
            assert key in data.get("published", {}), key
        assert c["source"].startswith("https://")
        m = spec_mod.Spec(ROOT).arch(data).Dims.of(data)
        assert m.H % m.KH == 0 and m.H * m.D == m.d


def test_metrics_list_cells_that_exist():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["workloads"]) <= cells
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in \
                m["name"]:
            assert m["unit"] == "%"
    # metrics of one layer name it letter for letter
    assert all(len(v) == 1 for v in layers.values()), layers


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_traffic_files_parse(name):
    mix = json.loads((ROOT / "benchmarks/chip/traffic" / f"{name}.json")
                     .read_text())
    kind = spec_mod.Spec(ROOT).traffic_kind(mix)
    assert callable(kind.generate) and callable(kind.max_context)
    assert mix["temperature"] == 0.0       # greedy, as the check needs
