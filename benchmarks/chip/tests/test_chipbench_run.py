"""The harness end to end on the CPU, at a test's size: with no TPU, or
on a chip whose peaks it does not know, it refuses; a cell added as data
alone runs and is correct, with a traffic kind and an architecture added
as files; a queued document-QA job hits the prefix cache and its check
covers a hit; a token altered where the decode step produces it (on a
prefix hit too), a request dropped after its first frame, and a request
ended early each make ``correct`` false."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import cells

RUN = cells.BENCH / "run.py"


def run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(RUN), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_exits_nonzero_without_a_tpu():
    p = run_py(cells.REPO, "--workload", "qwen4b-chat", "--seed",
               str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(cells.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen4b-chat", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and no_result(p.stdout)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload,e2e", [
    ("tiny-chat", {"tpot_p90_ms", "setup_s"}),
    ("tiny-long", {"tpot_p90_ms", "setup_s"}),
    ("tiny-docqa", {"output_tok_s", "setup_s"}),
    ("tiny-steady", {"tpot_p90_ms", "setup_s"})])
def test_a_cell_added_as_data_runs_correct(root, workload, e2e):
    res = cells.cpu_run(root, workload, 4)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == e2e
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert res["check"]["failed_requests"]["value"] == 0
    assert res["check"]["short_requests"]["value"] == 0


def test_a_traced_run_stops_its_trace_while_serving(root, monkeypatch):
    import harness
    monkeypatch.setattr(harness, "TRACE_S", 1.0)   # 0.5 s to 1.5 s of 2 s
    res = cells.cpu_run(root, "tiny-chat", 8, trace=True)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"decode_k1_share.chat"}
    assert res["device"]["window_s"] == pytest.approx(1.0, abs=0.2)
    assert list(res)[-1] == "check"


def test_an_unknown_chip_is_an_error(root, monkeypatch):
    import harness
    monkeypatch.setattr(harness, "require_chips", lambda n: None)
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    with pytest.raises(KeyError, match="no published peaks"):
        harness.run(root, "tiny-chat", 6, 2.0, False, require_chip=True)


def alter_first_step(eng):
    """The first step's token of every slot in each decode call is altered
    where the decode step produces it."""
    be = eng.backend
    decode = be.fused_decode

    def altered(K, host_state=None):
        toks, produced, done = decode(K, host_state)
        toks = toks.copy()
        toks[0] = (toks[0] + 1) % eng.model.cfg.vocab_size
        return toks, produced, done
    be.fused_decode = altered


def test_a_token_altered_where_produced_is_not_correct(root):
    res = cells.cpu_run(root, "tiny-chat", 5, fault=alter_first_step)
    assert not res["correct"]
    assert res["check"]["mean_logit_gap"]["value"] > \
        res["check"]["mean_logit_gap"]["limit"]


def test_the_widest_gap_is_compared_where_the_cell_states_it(tmp_path):
    """A cell whose check states ``max_logit_gap`` compares the widest gap
    too, right after the mean; a cell that states none compares only the
    mean (``qwen4b-chat``'s check is unchanged)."""
    r = cells.make_root(tmp_path)
    path = r / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg["check"]["max_logit_gap"] = 0.5
    path.write_text(json.dumps(cfg))
    ok = cells.cpu_run(r, "tiny-chat", 5)
    assert ok["correct"], ok["check"]
    assert list(ok["check"])[:2] == ["mean_logit_gap", "max_logit_gap"]
    assert ok["check"]["max_logit_gap"]["value"] <= 0.5
    bad = cells.cpu_run(r, "tiny-chat", 5, fault=alter_first_step)
    assert not bad["correct"]
    assert bad["check"]["max_logit_gap"]["value"] > 0.5


def drop_after_first_frame(eng):
    """Every other request is aborted once it has its first token."""
    step = eng.step
    gone = set()

    def stepped():
        out = step()
        for rid, run in list(eng.running.items()):
            n = int(rid[1:])
            if n % 2 and run.output_tokens and rid not in gone:
                gone.add(rid)
                eng.abort(rid)
        return out
    eng.step = stepped


def stop_at_half(eng):
    """Every request's stream ends with reason "stop" once it holds half
    the tokens the request asked for; later frames are not sent."""
    add = eng.add_request

    def cut(req, on_delta=None):
        seen, ended = [0], [False]

        def delta(frame):
            if ended[0]:
                return
            seen[0] += frame.n_tokens
            on_delta(frame)
            if not frame.finished and \
                    2 * seen[0] >= req.sampling.max_tokens:
                ended[0] = True
                on_delta(dataclasses.replace(
                    frame, tokens=[], n_tokens=0, finished=True,
                    finish_reason="stop"))
        return add(req, on_delta=delta)
    eng.add_request = cut


@pytest.mark.parametrize("fault,number", [
    (drop_after_first_frame, "failed_requests"),
    (stop_at_half, "short_requests")])
def test_requests_dropped_or_cut_short_are_not_correct(root, fault, number):
    not_correct_by(cells.cpu_run(root, "tiny-chat", 7, fault=fault), number)


def not_correct_by(res, number):
    """``correct`` false through ``number`` alone: the served tokens that
    did come are the reference's."""
    assert not res["correct"]
    assert res["check"][number]["value"] > 0, res["check"]
    assert res["check"]["mean_logit_gap"]["value"] <= \
        res["check"]["mean_logit_gap"]["limit"]


@pytest.mark.parametrize("fault,number", [
    (drop_after_first_frame, "failed_requests"),
    (stop_at_half, "short_requests")])
def test_docqa_requests_dropped_or_cut_short_are_not_correct(root, fault,
                                                             number):
    not_correct_by(cells.cpu_run(root, "tiny-docqa", 7, fault=fault), number)


def test_kinds_and_architectures_are_added_as_files_alone(root):
    """The checkout's benchmark is the repository's, file for file, with
    the fixtures beside it; the cells that name them resolve."""
    bench = root / "bench"
    for f in cells.BENCH.rglob("*"):
        rel = f.relative_to(cells.BENCH)
        if f.is_file() and rel.parts[0] != "tests" and \
                "__pycache__" not in rel.parts:
            assert (bench / rel).read_bytes() == f.read_bytes(), rel
    added = {p.relative_to(bench).as_posix() for p in bench.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts
             and not (cells.BENCH / p.relative_to(bench)).exists()}
    assert added == set(cells.ADDED.values())
    import spec
    cell = spec.Spec(root).cell("tiny-steady")
    assert Path(cell.arch.__file__).name == "dense_alias.py"
    assert Path(cell.kind.__file__).name == "steady.py"


def test_a_docqa_job_hits_the_prefix_cache_and_checks_a_hit(root,
                                                             monkeypatch):
    import harness
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    sampled = []
    pick = harness.sample_for_check

    def spy(rec, seed, check):
        out = pick(rec, seed, check)
        sampled.extend(out)
        return out
    monkeypatch.setattr(harness, "sample_for_check", spy)
    res = cells.cpu_run(root, "tiny-docqa", 9, trace=True)
    assert res["correct"], res["check"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert res["metrics"]["prefix_hit_share.docqa"]["value"] > 0
    assert max(r.cached for r in sampled) > 0


def alter_prefix_hits(eng):
    """Every decode token of a sequence admitted with a prefix-cache hit
    is altered where the decode step produces it."""
    be = eng.backend
    start, decode = be.start_prefill, be.fused_decode
    hits = set()

    def started(seq_id, prompt):
        task = start(seq_id, prompt)
        if task.cached_tokens:
            hits.add(seq_id)
        return task

    def altered(K, host_state=None):
        toks, produced, done = decode(K, host_state)
        toks = toks.copy()
        for rid in hits & set(eng.running):
            s = be.slot(rid)
            toks[:, s] = (toks[:, s] + 1) % eng.model.cfg.vocab_size
        return toks, produced, done
    be.start_prefill, be.fused_decode = started, altered


def test_a_token_altered_on_a_prefix_hit_is_not_correct(root):
    res = cells.cpu_run(root, "tiny-docqa", 9, fault=alter_prefix_hits)
    assert not res["correct"]
    assert res["check"]["mean_logit_gap"]["value"] > \
        res["check"]["mean_logit_gap"]["limit"]
    assert res["check"]["short_requests"]["value"] == 0
