"""A checkout of the benchmark with cells added from data alone: a copy
of the benchmark's directory, configurations, traffic mixes, a traffic
kind and an architecture from ``fixtures/``, a ``BENCHMARK.json`` that
names them, and the program's ``src`` beside them. Nothing that the copy
holds is edited: the fixtures are added beside it (``ADDED``)."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
BENCH = REPO / "benchmarks" / "chip"
FIX = Path(__file__).resolve().parent / "fixtures"
# fixture -> where a checkout gains it, under its benchmark's directory
ADDED = {"tiny.json": "configs/tiny.json",
         "tiny-alias.json": "configs/tiny-alias.json",
         "tiny-chat.json": "traffic/tiny-chat.json",
         "tiny-long.json": "traffic/tiny-long.json",
         "tiny-docqa.json": "traffic/tiny-docqa.json",
         "tiny-steady.json": "traffic/tiny-steady.json",
         "kinds/steady.py": "traffic/kinds/steady.py",
         "arch/dense_alias.py": "arch/dense_alias.py"}


def make_root(root: Path) -> Path:
    b = root / "bench"
    (root / "src").symlink_to(REPO / "src")
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for src, dst in ADDED.items():
        assert not (b / dst).exists(), dst
        shutil.copy(FIX / src, b / dst)
    chat, long, docqa = ["tiny-chat"], ["tiny-long"], ["tiny-docqa"]

    def metric(name, unit, cells, **kw):
        return {"name": name, "unit": unit, "better": "lower",
                "source": "host_clock", "workloads": cells, **kw}

    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "fixture",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"},
                    {"name": "tiny-alias", "source": "fixture",
                     "file": "bench/configs/tiny-alias.json", "reduced": [],
                     "why": "an architecture added as a file"}],
        "workloads": [
            {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-chat",
             "chips": 1, "why": "open loop"},
            {"name": "tiny-long", "config": "tiny",
             "traffic": "tiny-long", "chips": 1,
             "why": "prompts of several prefill chunks"},
            {"name": "tiny-docqa", "config": "tiny",
             "traffic": "tiny-docqa", "chips": 1,
             "why": "a queued job of questions over shared documents"},
            {"name": "tiny-steady", "config": "tiny-alias",
             "traffic": "tiny-steady", "chips": 1,
             "why": "a traffic kind and an architecture added as files"}],
        "end_to_end": [
            metric("tpot_p90_ms", "ms", chat + long + ["tiny-steady"],
                   bound=0.25),
            {"name": "output_tok_s", "unit": "tok/s", "better": "higher",
             "bound": 0.25, "source": "host_clock", "workloads": docqa},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            metric("decode_k1_share.chat", "%", chat, layer="engine",
                   moves="tpot_p90_ms"),
            metric("decode_step_ms.long", "ms", long, layer="model step",
                   moves="tpot_p90_ms"),
            metric("prefix_hit_share.docqa", "%", docqa,
                   layer="prefix cache", moves="output_tok_s"),
            metric("mfu.docqa", "%", docqa, layer="whole step",
                   moves="output_tok_s")]}
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
