"""One run of one cell: set-up, the measured window, the comparison.

Set-up makes the weights on the device from the seed, builds the engine
that the configuration names (its architecture's ``model_config`` and
``program_params``, ``arch/<arch>.py``), compiles every program shape that
the cell's traffic can reach (by driving the engine once and the backend's
prefill and decode calls at each chunk size, context bucket and decode
step count). The window then drives the engine as a client would: each
request is a typed
``/v1`` ``CompletionRequest``, converted by ``to_inference_request`` and
handed to ``ContinuousBatchingEngine.add_request(on_delta=...)`` when it
is due; the harness calls ``step()`` in its own loop and times every
stream frame on its own clock. After the window and the drain of every
request due in it, the program's arrays are freed and
``reference.compare`` checks a sample of the served tokens.

What a run records (``Records``) is what the metric readers in
``metrics/`` read; each reader returns None where it finds nothing.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import peaks as peaks_mod
import reference
import spec as spec_mod
import trace_reduce as TR
import traffic as traffic_mod

DRAIN_S = 120.0       # how long requests due in the window may take to
                      # finish after it closes (a 1024-token answer at
                      # ~100 ms a token, as measured on one v5e)
TRACE_S = 40.0        # traced part of a --trace 1 run, centred in the window
# Collecting and writing the trace takes about as long as it traced (one
# v5e) and releases the GIL: a thread does it while the engine serves on.
WARM_ID = 0           # token id of warm-up prompts; traffic draws from 2..V


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Req:
    rid: str
    due: float                 # perf_counter time the request is due
    prompt_len: int
    max_tokens: int
    prompt: list = field(repr=False, default=None)
    sent: float | None = None
    admitted: float | None = None      # first start_prefill
    cached: int = 0                    # prefix-cache hits at admission
    frames: list = field(default_factory=list)   # (time, n_tokens)
    tokens: list = field(default_factory=list)
    finished: float | None = None
    reason: str = ""

    def ctx(self) -> int:
        """KV entries the engine holds: every emitted token but the last."""
        return self.prompt_len + len(self.tokens) - 1


@dataclass
class Records:
    cell: object
    dims: object                       # the architecture's Dims
    page: int
    chips: int
    peaks: dict | None
    seconds: float
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)         # perf_counter bounds of the window
    drain_end: float = 0.0
    requests: list = field(default_factory=list)
    prefills: list = field(default_factory=list)   # (t, prompt, cached)
    prefill_calls: list = field(default_factory=list)  # (t0, t1, pos, n)
    decode_calls: list = field(default_factory=list)
    # decode_calls: (t0, t1, K asked, K run, [(ctx0, produced), ...])
    trace: TR.Trace | None = None
    trace_host: tuple | None = None    # perf_counter bounds of the trace
    compiles: dict = field(default_factory=dict)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def trace_share(self, t0: float, t1: float) -> float:
        """The share of a call's host interval [t0, t1] that lies inside
        the trace: a call that straddles an edge of the trace counts by
        that share, as its device time there is clipped to the trace."""
        if self.trace_host is None:
            return 0.0
        lo, hi = self.trace_host
        if t1 <= t0:
            return float(lo <= t0 < hi)
        return max(0.0, min(t1, hi) - max(t0, lo)) / (t1 - t0)


class CompileCounter:
    """Counts JAX traces and XLA compiles while ``on``."""

    def __init__(self):
        self.on = False
        self.counts: dict = {}

    def __call__(self, event: str, duration: float, **_):
        if self.on and ("jaxpr_trace_duration" in event
                        or "backend_compile_duration" in event):
            key = event.rsplit("/", 1)[-1]
            self.counts[key] = self.counts.get(key, 0) + 1


def require_chips(n: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"[bench] needs a TPU: JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"[bench] the cell needs {n} chips, JAX found "
                         f"{len(devs)}")


def setup_compile_cache() -> str:
    """JAX's persistent cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program kept, however quick its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span_factory(on: bool):
    import jax

    def span(name):
        return jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + name) if on \
            else contextlib.nullcontext()
    return span


def build_engine(cell, m, seed: int):
    """The program under test, with the seed's weights."""
    import jax
    from repro.models import make_model
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig

    c = cell.config
    model = make_model(cell.arch.model_config(c, m))
    params = jax.block_until_ready(cell.arch.program_params(m, seed))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise SystemExit(f"[bench] weights do not match the program's "
                         f"parameters: {got} vs {want}")
    return ContinuousBatchingEngine(model, params,
                                    EngineConfig(**c["engine"]))


def warm_up(eng, max_context: int, model_name: str) -> int:
    """Compile every program shape the window can reach; returns the
    number of backend calls made. Warm-up prompts start with token 0,
    which the traffic never sends, so their cached pages never match a
    request's."""
    from repro.serving.request import InferenceRequest, SamplingParams

    ec = eng.cfg
    be = eng.backend
    ps, budget, K = ec.page_size, ec.chunked_prefill_budget, \
        ec.decode_steps_per_sync
    reach = min(ec.max_seq_len - 1, max_context)
    # the engine end to end: first-token sampling, K=1 after admission, K
    eng.add_request(InferenceRequest(
        model=model_name, prompt_tokens=[WARM_ID] * (ps + 1),
        sampling=SamplingParams(max_tokens=2 * K + 2), request_id="warm"))
    eng.run_to_completion()
    calls = 0
    sizes = [16]
    while sizes[-1] < budget:
        sizes.append(min(2 * sizes[-1], budget))
    ends, p = [], 1
    while True:
        ends.append(min(p * ps, reach))
        if p * ps >= reach:
            break
        p *= 2
    tag = 0

    def prompt(n):          # a first page of its own: no prefix hits
        nonlocal tag
        tag += 1
        return [WARM_ID, tag] + [WARM_ID] * (n - 2)

    # every chunk size against every context bucket: the chunk alone is
    # computed, at the end of a prompt of that length
    for n in sizes:
        for e in sorted({max(n, x) for x in ends if max(n, x) <= reach}):
            task = be.start_prefill("warm", prompt(e))
            task.pos = e - n
            be.prefill_chunk(task, n)
            be.free("warm")
            calls += 1
    # a prompt that the cache holds whole: its last token is computed
    # again, into a copy of the shared page
    toks = [WARM_ID, eng.model.cfg.vocab_size - 1] + [WARM_ID] * (2 * ps - 2)
    for _ in range(2):
        task = be.start_prefill("warm", toks)
        while not task.done:
            be.prefill_chunk(task, budget)
            calls += 1
        be.free("warm")
    # every decode step count the pool can clamp K to
    idle = eng.slots.host_state()
    for k in range(1, K + 1):
        be.fused_decode(k, idle)
        calls += 1
    eng.slots.dirty = True
    return calls


def instrument(eng, rec: Records, span):
    """Record each call the engine makes into its backend, on the
    harness's clock, with what the cost readers need."""
    be = eng.backend
    orig = be.__dict__.setdefault("_bench_orig", (
        be.start_prefill, be.prefill_chunk, be.fused_decode))
    start_prefill, prefill_chunk, fused_decode = orig
    by_id = {r.rid: r for r in rec.requests}
    pc = time.perf_counter

    def w_start_prefill(seq_id, prompt):
        t = pc()
        with span("admit"):
            task = start_prefill(seq_id, prompt)
        rec.prefills.append((t, len(task.prompt), task.cached_tokens))
        r = by_id.get(seq_id)
        if r is not None and r.admitted is None:
            r.admitted, r.cached = t, task.cached_tokens
        return task

    def w_prefill_chunk(task, budget=None):
        pos, t0 = task.pos, pc()
        with span("prefill"):
            out = prefill_chunk(task, budget)
        rec.prefill_calls.append((t0, pc(), pos, out[1]))
        return out

    def w_fused_decode(K, host_state=None):
        live = [(be.slot(rid), by_id[rid].ctx()) for rid in eng.running
                if rid in by_id]
        t0 = pc()
        with span("decode"):
            toks, produced, done = fused_decode(K, host_state)
        rec.decode_calls.append((t0, pc(), K, toks.shape[0],
                                 [(c, int(produced[s])) for s, c in live]))
        return toks, produced, done

    be.start_prefill = w_start_prefill
    be.prefill_chunk = w_prefill_chunk
    be.fused_decode = w_fused_decode


def typed_requests(reqs, model_name: str, mix: dict):
    from repro.api.schemas import CompletionRequest
    return [CompletionRequest(
        model=model_name, prompt_tokens=r.prompt, request_id=r.rid,
        max_tokens=r.max_tokens, temperature=float(mix["temperature"]),
        qos=mix.get("qos", "interactive")).validate() for r in reqs]


def sample_for_check(rec: Records, seed: int, check: dict) -> list:
    """Finished requests to compare: the one with the most served tokens,
    the one with the longest prompt, the one whose admission found the
    most prompt tokens in the prefix cache (where any found some), then
    others drawn from the seed until the sample holds ``sample_tokens``
    served tokens or ``sample_requests`` requests."""
    done = [r for r in rec.requests if r.finished is not None and r.tokens]
    if not done:
        return []
    rng = np.random.default_rng(seed + 1)
    pick = [max(done, key=lambda r: len(r.tokens)),
            max(done, key=lambda r: r.prompt_len)]
    hit = max(done, key=lambda r: r.cached)
    if hit.cached:
        pick.append(hit)
    pick = list({id(r): r for r in pick}.values())
    for i in rng.permutation(len(done)):
        if (sum(len(r.tokens) for r in pick) >= check["sample_tokens"]
                or len(pick) >= check["sample_requests"]):
            break
        if all(done[i] is not r for r in pick):
            pick.append(done[i])
    return pick


class Session:
    """The program under test after set-up: weights made, engine built,
    every shape the traffic mix can reach compiled."""

    def __init__(self, root: Path, workload: str, seed: int, *,
                 require_chip: bool = True, t_start: float | None = None):
        self.t_start = t_start or time.time()
        self.root = Path(root)
        self.spec = spec_mod.Spec(self.root)
        self.cell = cell = self.spec.cell(workload)
        import jax
        if require_chip:
            require_chips(cell.chips)
        sys.path.insert(0, str(self.root / "src"))
        log(f"compile cache: {setup_compile_cache()}")
        self.devs = jax.devices()
        self.kind = self.devs[0].device_kind
        # a chip that the table does not know is an error; the CPU of a
        # test run has no peaks, and the readers of shares find nothing
        self.peaks = peaks_mod.peaks(self.kind) if require_chip \
            else peaks_mod.PEAKS.get(self.kind)
        self.dims = cell.arch.Dims.of(cell.config)
        self.counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(self.counter)
        self.eng = build_engine(cell, self.dims, seed)
        t = time.time()
        n = warm_up(self.eng, cell.kind.max_context(cell.traffic),
                    cell.config["name"])
        log(f"warm-up: {n} backend calls in {time.time() - t:.1f}s")

    def window(self, mix: dict, seed: int, seconds: float, trace: bool, *,
               fault=None) -> Records:
        """Serve one run of ``mix`` and record it; the requests due in the
        window drain after it closes."""
        import jax
        from repro.api import to_inference_request

        eng, cell = self.eng, self.cell
        reqs = self.spec.traffic_kind(mix).generate(mix, seed, seconds,
                                                    self.dims.V)
        log(f"traffic: {traffic_mod.describe(reqs)}")
        typed = typed_requests(reqs, cell.config["name"], mix)
        rec = Records(cell=cell, dims=self.dims, page=eng.cfg.page_size,
                      chips=cell.chips, seconds=seconds, peaks=self.peaks,
                      requests=[Req(r.rid, r.due, len(r.prompt),
                                    r.max_tokens, prompt=r.prompt)
                                for r in reqs])
        span = span_factory(trace)
        instrument(eng, rec, span)
        if fault is not None:
            fault(eng)
        pc = time.perf_counter
        by_id = {r.rid: r for r in rec.requests}

        def on_delta(frame):
            r = by_id[frame.id]
            now = pc()
            if frame.n_tokens:
                r.frames.append((now, frame.n_tokens))
                r.tokens.extend(frame.tokens)
            if frame.finished:
                r.finished, r.reason = now, frame.finish_reason

        def send(i):
            with span("admit"):
                rec.requests[i].sent = pc()
                eng.add_request(to_inference_request(typed[i]),
                                on_delta=on_delta)

        n, sent = len(typed), 0
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else None
        trace_at = max(0.0, (seconds - TRACE_S) / 2)
        tracing, ann, stopper = False, None, None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans and device ops only
        self.counter.counts = {}
        self.counter.on = True
        t0 = pc()
        rec.setup_s = time.time() - self.t_start
        rec.window = (t0, t0 + seconds)
        for r in rec.requests:
            r.due += t0
        close = t0 + seconds
        limit = close + DRAIN_S
        while True:
            now = pc()
            if trace:
                if not tracing and ann is None and now >= t0 + trace_at:
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                    ann = jax.profiler.TraceAnnotation(
                        TR.SPAN_PREFIX + "window")
                    ann.__enter__()
                    tracing, rec.trace_host = True, (pc(), None)
                elif tracing and now >= t0 + trace_at + TRACE_S:
                    rec.trace_host = (rec.trace_host[0], pc())
                    ann.__exit__(None, None, None)
                    stopper = threading.Thread(target=jax.profiler.stop_trace)
                    stopper.start()
                    tracing = False
            while sent < n and rec.requests[sent].due <= now:
                send(sent)
                sent += 1
            if now >= limit or (sent == n and not eng.has_work()):
                break
            if eng.has_work():
                with span("step"):
                    eng.step()
            else:
                nxt = rec.requests[sent].due if sent < n else limit
                with span("wait"):
                    time.sleep(max(0.0, min(nxt, limit) - pc()))
        rec.drain_end = pc()
        if tracing:
            rec.trace_host = (rec.trace_host[0], pc())
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if stopper is not None:
            stopper.join()
        self.counter.on = False
        rec.compiles = dict(self.counter.counts)
        log(f"window {seconds}s closed; drain {rec.drain_end - close:.1f}s; "
            f"traces and compiles in the window: {rec.compiles or 0}")
        if trace:
            log(f"trace written {pc() - rec.drain_end:.1f}s after the drain")
            paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            if paths:
                rec.trace = TR.reduce_trace(paths[-1])
            shutil.rmtree(trace_dir, ignore_errors=True)
        return rec

    def peak_bytes(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devs[:self.cell.chips])

    def free(self) -> None:
        """Delete every array the program holds."""
        import jax
        self.eng = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()


AT_LEAST = {"served_tokens_compared"}     # the other numbers are maxima


def passes(check: dict) -> bool:
    """Every compared number within its limit."""
    return all(v["value"] >= v["limit"] if k in AT_LEAST
               else v["value"] <= v["limit"] for k, v in check.items())


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, control: bool = False, require_chip: bool = True,
        t_start: float | None = None,
        fault=None) -> dict:
    """One run; returns the result line as a dict. ``fault(eng)``, for
    tests only, breaks the timed path after set-up."""
    ses = Session(root, workload, seed, require_chip=require_chip,
                  t_start=t_start)
    cell = ses.cell
    rec = ses.window(cell.traffic, seed, seconds, trace,
                     fault=fault)
    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        value = ses.spec.reader(entry["name"])(rec)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    d0 = ses.devs[0]
    device = {"platform": d0.platform, "kind": ses.kind,
              "count": len(ses.devs), "memory_peak_bytes": ses.peak_bytes()}
    out = {"metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_ns / 1e9
        device["window_s"] = rec.trace.window_ns / 1e9
        out["breakdown"] = {"device_ops": TR.top_ops(rec.trace),
                            "idle_gaps": TR.idle_gaps(rec.trace)}
    # every request due in the window has to finish with all the tokens it
    # asked for: greedy, with no stop token, nothing ends a request early
    due = rec.requests
    failed = sum(r.finished is None for r in due)
    short = sum(len(r.tokens) != r.max_tokens for r in due)

    # the comparison: free the program's arrays, then the reference
    check_cfg = cell.config["check"]
    sample = sample_for_check(rec, seed, check_cfg)
    pairs = [(r.prompt, list(r.tokens)) for r in sample]
    ses.free()
    t = time.time()
    cmp = reference.compare(cell.arch, ses.dims, seed, pairs, control) \
        if pairs else None
    log(f"reference over {len(pairs)} requests, "
        f"{cmp['served_tokens'] if cmp else 0} served tokens: "
        f"{time.time() - t:.1f}s")

    def checks(mean_gap, widest_gap):
        out = {"mean_logit_gap": {"value": mean_gap,
                                  "limit": check_cfg["max_mean_logit_gap"]}}
        if "max_logit_gap" in check_cfg:        # where the cell compares it
            out["max_logit_gap"] = {"value": widest_gap,
                                    "limit": check_cfg["max_logit_gap"]}
        return {**out,
                "failed_requests": {"value": failed, "limit": 0},
                "short_requests": {"value": short, "limit": 0},
                "served_tokens_compared": {
                    "value": cmp["served_tokens"] if cmp else 0,
                    "limit": check_cfg["min_compared_tokens"]}}

    check = checks(*((cmp["mean_gap"], cmp["logit_gap"]) if cmp
                     else (float("inf"), float("inf"))))
    if cmp is not None:
        log(f"argmax match with the reference: {cmp['argmax_match']:.4f}; "
            f"widest gap {cmp['logit_gap']}")
        if control:
            ctl = checks(cmp["control_mean_gap"], cmp["control_gap"])
            out["control"] = {"correct": passes(ctl),
                              "mean_logit_gap": cmp["control_mean_gap"],
                              "widest_gap": cmp["control_gap"]}
            log(f"control (int8): correct {passes(ctl)}; mean gap "
                f"{cmp['control_mean_gap']} (limit <= "
                f"{check_cfg['max_mean_logit_gap']}), widest gap "
                f"{cmp['control_gap']}")
    for k, v in check.items():
        op = ">=" if k in AT_LEAST else "<="
        log(f"check {k}: {v['value']} (limit {op} {v['limit']})")
    return {"correct": passes(check), "attempted": len(due),
            "failed": int(failed), **out, "check": check}
