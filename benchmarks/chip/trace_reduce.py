"""From a profiler trace (``.xplane.pb``) to the device's busy time, each
device operation's time, and the harness's host spans.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip, named by its HLO text
(``%fusion.12 = bf16[...] fusion(...)``; a loop's event spans the events
of its body), their ``XLA Modules`` line one per compiled program run
(``jit__chunk_prefill_impl(<id>)``). Host spans are the events whose names start with
``bench.``: the harness puts them around its own calls into the program
(``jax.profiler.TraceAnnotation``), on the same clock as the device.
The reduction keeps to the traced window, the ``bench.window`` span.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"[.(]\d+\)?$")
# control flow whose events enclose their body's operations
CONTAINERS = {"while", "conditional", "call"}


@dataclass
class Trace:
    window: tuple                   # (start_ns, end_ns) of bench.window
    chips: int                      # device planes with operations
    busy_ns: float                  # union of op intervals, mean over chips
    ops: list                       # (op name, start_ns, dur_ns), chip 0
    modules: list                   # (program, start_ns, dur_ns), chip 0
    spans: list                     # (name, start_ns, end_ns) host spans
    busy: np.ndarray = field(repr=False, default=None)   # merged, chip 0

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``jit_f(3)`` -> ``jit_f``."""
    return _SUFFIX.sub("", name)


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, float)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce_trace(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    win = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    if not win:
        raise ValueError(f"{path}: no {SPAN_PREFIX}window span")
    lo, hi = win[0][1], win[0][2]
    spans = [s for s in spans if s[2] > lo and s[1] < hi
             and s[0] != SPAN_PREFIX + "window"]
    busy, ops, modules, first = [], [], [], None
    for plane in sorted(devices, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        evs = [ev for ev in lines["XLA Ops"].events
               if ev.end_ns > lo and ev.start_ns < hi]
        if not evs:
            continue
        iv = _merge(_clip(np.array([[ev.start_ns, ev.end_ns] for ev in evs],
                                   float), lo, hi))
        busy.append(iv)
        if first is None:
            first = iv
            ops = [(op_name(ev.name), ev.start_ns, ev.duration_ns)
                   for ev in evs]
            mod_line = lines.get("XLA Modules")
            for ev in mod_line.events if mod_line is not None else []:
                if ev.end_ns > lo and ev.start_ns < hi:
                    modules.append((base_name(ev.name), ev.start_ns,
                                    ev.duration_ns))
    busy_ns = float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in busy])) \
        if busy else 0.0
    return Trace(window=(lo, hi), chips=len(busy), busy_ns=busy_ns,
                 ops=ops, modules=modules, spans=spans,
                 busy=first if first is not None else np.zeros((0, 2)))


def op_seconds(tr: Trace, pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``,
    clipped to the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return sum(min(s + d, hi) - max(s, lo) for n, s, d in tr.ops
               if rx.search(n)) / 1e9


def module_seconds(tr: Trace, pattern: str) -> float:
    rx = re.compile(pattern)
    lo, hi = tr.window
    return sum(min(s + d, hi) - max(s, lo) for n, s, d in tr.modules
               if rx.search(n)) / 1e9


def top_ops(tr: Trace, n: int = 10) -> list:
    """The n operations, grouped by name without their numeric suffix,
    that took the most device time in the window; loops, whose events
    enclose their body's, are left out."""
    acc: dict = {}
    lo, hi = tr.window
    for name, s, d in tr.ops:
        k = base_name(name)
        if k not in CONTAINERS:
            acc[k] = acc.get(k, 0.0) + (min(s + d, hi) - max(s, lo)) / 1e9
    return sorted(([k, v] for k, v in acc.items()), key=lambda x: -x[1])[:n]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The n longest gaps with no operation on the device, each named by
    the innermost harness span that the host was in at the gap's middle
    (``host_idle`` where it was in none)."""
    lo, hi = tr.window
    b = tr.busy
    edges = np.concatenate([[lo], b.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:n]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        inside = [sp for sp in tr.spans if sp[1] <= mid < sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0][
            len(SPAN_PREFIX):] if inside else "host_idle"
        out.append([name, (e - s) / 1e9])
    return out
