"""A checkout of the benchmark with cells added from data alone: a copy
of the benchmark's directory, a configuration and traffic mixes from
``fixtures/``, a ``BENCHMARK.json`` that names them, and the program's
``src`` beside them."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
BENCH = REPO / "benchmarks" / "chip"
FIX = Path(__file__).resolve().parent / "fixtures"


def make_root(root: Path) -> Path:
    b = root / "bench"
    (root / "src").symlink_to(REPO / "src")
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(FIX / "tiny.json", b / "configs" / "tiny.json")
    for mix in ("tiny-chat", "tiny-long"):
        shutil.copy(FIX / f"{mix}.json", b / "traffic" / f"{mix}.json")
    chat, long = ["tiny-chat"], ["tiny-long"]

    def metric(name, unit, cells, **kw):
        return {"name": name, "unit": unit, "better": "lower",
                "source": "host_clock", "workloads": cells, **kw}

    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "fixture",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-chat",
             "chips": 1, "why": "open loop"},
            {"name": "tiny-long", "config": "tiny",
             "traffic": "tiny-long", "chips": 1,
             "why": "prompts of several prefill chunks"}],
        "end_to_end": [
            metric("tpot_p90_ms", "ms", chat + long, bound=0.25),
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            metric("decode_k1_share.chat", "%", chat, layer="engine",
                   moves="tpot_p90_ms"),
            metric("decode_step_ms.long", "ms", long, layer="model step",
                   moves="tpot_p90_ms")]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_run(root, workload, seed, trace=False, **kw):
    """``harness.run`` on the CPU, the chip check and the persistent
    compile cache left out."""
    import harness
    saved = harness.setup_compile_cache
    harness.setup_compile_cache = lambda: "off"
    try:
        return harness.run(root, workload, seed, 2.0, trace,
                           require_chip=False, **kw)
    finally:
        harness.setup_compile_cache = saved
