"""What the program's own instrumentation leaves in a profiler trace.

``trace_reduce`` keeps the device's ops and the harness's ``bench.``
spans. The program adds two things of its own (``serving/engine.py``,
``serving/backends.py``, ``serving/sampler.py``):

- scopes: each device op's scope path, e.g.
  ``jit(fused_decode)/while/body/sample/sort:``, from ``jax.named_scope``
  (``sample``, ``decode_attention``) and the programs' names. The trace
  keeps it as the ``tf_op`` stat of the op's event metadata, which
  ``jax.profiler.ProfileData`` does not expose, so it is read here from
  the ``.xplane.pb`` with a small protobuf wire-format reader (no
  TensorFlow or xprof import);
- host spans named ``engine.*`` (step, admit, prefill, decode and the
  decode's prep, wait and unpack), on the device's clock.

``read`` gathers both for the traced window; the functions below reduce
them. ``tools/engine_trace.py`` reports them beside a run's result line.
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

import trace_reduce as TR

ENGINE_PREFIX = "engine."
# field numbers (xplane.proto): XSpace.planes; XPlane.name, .event_metadata,
# .stat_metadata; a map entry's value; XEventMetadata.name, .stats;
# XStatMetadata.id and XStat.metadata_id; XStat.str_value
_PLANES, _NAME, _EVENT_META, _STAT_META = 1, 2, 4, 5
_ENTRY_VALUE, _META_STATS, _ID, _STR = 2, 5, 1, 5


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, lo, hi):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    varints, (start, end) for length-delimited fields; fixed-width fields
    are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entry_value(buf, span):
    return next((v for f, v in _fields(buf, *span) if f == _ENTRY_VALUE),
                None)


def op_scopes(path) -> tuple[dict, int]:
    """{op event name (its HLO text): scope path} over the device planes,
    and how many names carry more than one scope (the same text in two
    programs; the first scope read is kept)."""
    buf = memoryview(Path(path).read_bytes())
    out, conflicts = {}, set()
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _PLANES:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == _NAME:
                name = _text(buf, v)
            elif pf == _EVENT_META:
                metas.append(v)
            elif pf == _STAT_META:
                sm = _entry_value(buf, v)
                if sm is not None:
                    d = dict(_fields(buf, *sm))
                    if _NAME in d:
                        stat_names[d[_ID]] = _text(buf, d[_NAME])
        if not _is_device(name):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for v in metas:
            em = _entry_value(buf, v)
            if em is None:
                continue
            ev_name, scope = None, None
            for ef, ev in _fields(buf, *em):
                if ef == _NAME:
                    ev_name = _text(buf, ev)
                elif ef == _META_STATS:
                    d = dict(_fields(buf, *ev))
                    if d.get(_ID) in tf_op and _STR in d:
                        scope = _text(buf, d[_STR])
            if ev_name is None or scope is None:
                continue
            if out.setdefault(ev_name, scope) != scope:
                conflicts.add(ev_name)
    return out, len(conflicts)


def _is_device(name: str) -> bool:
    return name.startswith("/device:TPU:") \
        and name[len("/device:TPU:"):].isdigit()


@dataclass
class ProgramTrace:
    window: tuple          # (start_ns, end_ns): the bench.window span
    ops: list              # (scope path, start_ns, dur_ns), first chip
    spans: list            # (name, start_ns, end_ns, stats) engine spans
    conflicts: int = 0     # op names read with more than one scope


def read(path, window: tuple) -> ProgramTrace:
    """The ops of the first device plane with their scopes, and the
    ``engine.`` host spans, that overlap ``window`` (ns)."""
    from jax.profiler import ProfileData

    scopes, conflicts = op_scopes(path)
    lo, hi = window
    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    devices = sorted((p for p in pd.planes if _is_device(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in devices:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(scopes.get(ev.name, ""), ev.start_ns, ev.duration_ns)
                       for ev in line.events
                       if ev.end_ns > lo and ev.start_ns < hi
                       and TR.base_name(TR.op_name(ev.name))
                       not in TR.CONTAINERS]
        if ops:
            break
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ENGINE_PREFIX) and ev.end_ns > lo \
                        and ev.start_ns < hi:
                    spans.append((ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
    return ProgramTrace(window=(lo, hi), ops=ops, spans=spans,
                        conflicts=conflicts)


def scope_seconds(pt: ProgramTrace, pattern: str) -> float:
    """Device seconds, clipped to the window, of the ops whose scope path
    matches ``pattern``; loops, whose events enclose their body's, are
    left out when the trace is read."""
    rx = re.compile(pattern)
    lo, hi = pt.window
    return sum(min(s + d, hi) - max(s, lo) for sc, s, d in pt.ops
               if rx.search(sc)) / 1e9


def span_seconds(pt: ProgramTrace, name: str) -> list:
    """Durations (s) of the spans named ``name``, clipped to the window."""
    lo, hi = pt.window
    return [(min(e, hi) - max(s, lo)) / 1e9 for n, s, e, _ in pt.spans
            if n == name]


SAMPLE_IN_DECODE = r"^jit\(fused_decode\)/.*\bsample/"


def decode_sample_ms(pt: ProgramTrace, rec) -> float | None:
    """Device ms of the ``sample`` scope in ``fused_decode`` programs per
    decode step in the trace (each call by the share of its host interval
    inside the trace, times the steps it ran)."""
    steps = sum(rec.trace_share(t0, t1) * k_run
                for t0, t1, _, k_run, _ in rec.decode_calls)
    t = scope_seconds(pt, SAMPLE_IN_DECODE)
    return 1e3 * t / steps if steps and t > 0 else None


def decode_host_ms(pt: ProgramTrace) -> float | None:
    """Host ms in ``engine.decode.prep`` and ``engine.decode.unpack`` per
    ``engine.decode`` span in the window."""
    n = len(span_seconds(pt, "engine.decode"))
    if not n:
        return None
    return 1e3 * (sum(span_seconds(pt, "engine.decode.prep"))
                  + sum(span_seconds(pt, "engine.decode.unpack"))) / n


def clamp_shares(opened: dict, closed: dict) -> dict:
    """Share (%) of the fused calls between two copies of the engine's
    stats that ran below K, by reason."""
    calls = closed["decode_syncs"] - opened["decode_syncs"]
    if calls <= 0:
        return {}
    return {k: 100.0 * (closed[k] - opened[k]) / calls
            for k in ("k1_prefill", "k1_batch", "k_pool")}


def clock_offset_ms(pt: ProgramTrace, modules: list) -> list:
    """For each ``engine.decode.wait`` span, its end minus the device end
    of the ``fused_decode`` program run nearest to it (ms): how long after
    the device finished the host had its tokens, plus any offset between
    the two clocks."""
    ends = np.sort([s + d for n, s, d in modules
                    if n == "jit_fused_decode"])
    if not len(ends):
        return []
    out = []
    for n, _, e, _ in pt.spans:
        if n != "engine.decode.wait":
            continue
        i = np.searchsorted(ends, e)
        near = [ends[j] for j in (i - 1, i) if 0 <= j < len(ends)]
        out.append((e - min(near, key=lambda x: abs(e - x))) / 1e6)
    return out


def idle_gaps(tr, pt: ProgramTrace, n: int = 10) -> list:
    """``trace_reduce.idle_gaps`` with each gap named by the innermost
    span of either kind the host was in at its middle: a harness span
    without its ``bench.`` prefix, a program span with its ``engine.``
    prefix, ``host_idle`` where it was in none."""
    # trace_reduce names a gap after its span less the bench. prefix:
    # a program span goes in with that prefix added, and comes out whole
    spans = tr.spans + [(TR.SPAN_PREFIX + name, s, e)
                        for name, s, e, _ in pt.spans]
    return TR.idle_gaps(dataclasses.replace(tr, spans=spans), n)
