"""``tools/timeline.py`` on the CPU: the run's result line is run.py's, with
the window's time split by the backend calls and the engine's counters
over the window beside it."""
import pytest

import cells
import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("checkout"))


def test_a_docqa_run_reports_where_its_window_went(root, monkeypatch):
    from tools import timeline
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    res = timeline.timed_run(root, "tiny-docqa", 9, 2.0, require_chip=False)
    assert res["correct"], res["check"]
    assert list(res)[-2:] == ["timeline", "check"]
    tl = res["timeline"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    # the job is served whole: every answer token is counted once, and the
    # queued prompts' shared documents are found in the prefix cache
    assert tl["stats"]["finished"] == res["attempted"] == 16
    assert tl["stats"]["cached_prompt_tokens"] > 0
    assert tl["calls"]["prefill"] > 0 and tl["calls"]["decode"] > 0
    assert 0 < tl["prefill_s"] + tl["decode_s"] < tl["makespan_s"]
    assert tl["outside_s"] == pytest.approx(
        tl["makespan_s"] - tl["prefill_s"] - tl["decode_s"], abs=1e-6)
    # the metric's makespan is the timeline's: the job's answer tokens, as
    # the engine counts them, over it
    assert res["metrics"]["output_tok_s"]["value"] == pytest.approx(
        tl["stats"]["decode_tokens"] / tl["makespan_s"])
    assert len(tl["gaps"]) <= timeline.TOP
    assert all(g[1] >= 0 for g in tl["gaps"])
    assert tl["rusage"]["utime"] >= 0 and len(tl["loadavg"]) == 2
    assert tl["steal"] is None or 0 <= tl["steal"] <= 1
    # the patch is undone: a plain run has no timeline
    assert harness.Session.window.__name__ == "window"
