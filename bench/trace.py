"""Reduce a profiler trace to device busy time, its split, and idle gaps.

Read from the ``.xplane.pb`` that ``jax.profiler`` writes:

* device planes ``/device:TPU:<n>``, line ``XLA Ops``: one event per HLO
  instruction executed, named by the instruction's text
  (``%name = shape opcode(...)``).  Control-flow containers (``while``,
  ``conditional``, ``call``) span their bodies' events and are left out;
  the ``Async XLA Ops`` line is left out too (in-flight copies overlap the
  ops that do the work).
* host planes: the benchmark's own spans (``bench.*``), used to say what
  the host was doing while the device sat idle.

Kernels are told from other ops by the compiled programs' text: an
instruction whose ``custom_call_target`` is ``tpu_custom_call`` is a
Mosaic kernel, one whose opcode is a collective is a collective.  Nothing
is guessed from a name.

Every reduction takes plain ``(name, start_ns, end_ns)`` tuples, so it can
be checked on a hand-built event set.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Set, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (instruction name, start_ns, end_ns)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_EVENT = re.compile(r"^%([^\s=]+)\s*=")
_OPCODE = re.compile(r"(?<![\w-])([a-z][\w-]*)\(")
_COLLECTIVE = re.compile(
    r"(?<![\w-])(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-broadcast)(-start|-done)?\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


# -- compiled programs --------------------------------------------------------

def classify_hlo(texts: Iterable[str]) -> Tuple[Set[str], Set[str]]:
    """(kernel instruction names, collective instruction names)."""
    kernels, collectives = set(), set()
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            if 'custom_call_target="tpu_custom_call"' in rest:
                kernels.add(name)
            elif _COLLECTIVE.search(rest):
                collectives.add(name)
    return kernels, collectives


def event_name(text: str) -> str:
    m = _EVENT.match(text)
    return m.group(1) if m else text


def is_container(text: str) -> bool:
    """A control-flow op, whose event spans the events of its body."""
    return text.startswith("%") and _first_opcode(text) in ("while", "conditional", "call")


def _first_opcode(text: str) -> str:
    """The opcode of an instruction's text: the first lowercase ``word(``
    after the result shape (a shape's only ``X(`` are layout tiles ``T(``
    and memory spaces ``S(``)."""
    m = _OPCODE.search(text.split("=", 1)[-1])
    return m.group(1) if m else ""


# -- intervals ------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of ``[lo, hi]`` that no merged interval covers."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [g for g in out if g[1] > g[0]]


def _host_span_at(t: float, spans: Sequence[Tuple[float, float, str]],
                  starts: Sequence[float]) -> str:
    """The innermost benchmark span open at ``t`` (spans sorted by start)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - 64), -1):
        if spans[j][1] >= t:
            return spans[j][2]
    return "no benchmark span"


# -- the reduction --------------------------------------------------------------

@dataclasses.dataclass
class DeviceStats:
    busy_s: float
    kernel_s: float
    collective_s: float
    other_s: float
    exposed_collective_s: float


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: Dict[int, DeviceStats]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def busy_s_mean(self) -> float:
        return sum(d.busy_s for d in self.devices.values()) / max(1, len(self.devices))

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def reduce_events(device_events: Dict[int, List[Event]], host_spans: List[Event],
                  kernels: Set[str], collectives: Set[str],
                  window: Interval) -> Reduction:
    """Reduce device events (containers already removed) over ``window``.

    Per device: busy is the union of every op's interval; kernel, collective
    and other time are the unions of each class, each a share of busy
    time when divided by it (they can overlap only through async ops).
    Exposed collective time is the part of the collectives' union that no
    other op covers.  ``top_ops`` sums device time by instruction and
    class over all devices, divided by the device count; ``idle_gaps``
    sums device 0's idle stretches by the benchmark span open on the host
    at each stretch's midpoint.
    """
    lo, hi = window
    stats: Dict[int, DeviceStats] = {}
    per_op: Dict[str, float] = {}
    n_dev = max(1, len(device_events))
    for dev, events in device_events.items():
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]
        allu = union((s, e) for _, s, e in evs)
        ker = union((s, e) for n, s, e in evs if n in kernels)
        col = union((s, e) for n, s, e in evs if n in collectives)
        oth = union((s, e) for n, s, e in evs if n not in kernels and n not in collectives)
        comp = union(list(ker) + list(oth))
        stats[dev] = DeviceStats(busy_s=measure(allu) / 1e9, kernel_s=measure(ker) / 1e9,
                                 collective_s=measure(col) / 1e9, other_s=measure(oth) / 1e9,
                                 exposed_collective_s=minus(col, comp) / 1e9)
        for n, s, e in evs:
            kind = "kernel" if n in kernels else "collective" if n in collectives else "op"
            key = f"{n} [{kind}]"
            per_op[key] = per_op.get(key, 0.0) + (e - s) / 1e9 / n_dev
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle: Dict[str, float] = {}
    if device_events:
        busy0 = union((s, e) for _, s, e in device_events[min(device_events)])
        spans = sorted((s, e, n) for n, s, e in host_spans if n != WINDOW_SPAN)
        starts = [s for s, _, _ in spans]
        for gs, ge in gaps(busy0, lo, hi):
            name = _host_span_at((gs + ge) / 2, spans, starts)
            idle[name] = idle.get(name, 0.0) + (ge - gs) / 1e9
    return Reduction(window_s=(hi - lo) / 1e9, devices=stats, top_ops=top,
                     idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:10])


def load(path: str):
    """(device events by device id, benchmark host spans) from an xplane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_events: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = device_events.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = ev.name
                    if is_container(text):
                        continue
                    evs.append((event_name(text), ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return device_events, spans


def window_of(host_spans: Sequence[Event]) -> Interval:
    """The traced window: the one host span ``bench.window``.

    Nothing stands in for it: the device events' own extent would leave
    out the idle stretches at the window's ends.
    """
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"the trace holds {len(win)} {WINDOW_SPAN} spans, not one")
    return win[0]


def reduce_dir(trace_dir: str, hlo_texts: Sequence[str], n_devices: int) -> Reduction:
    """Reduce the one xplane under ``trace_dir`` over its ``bench.window``."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device_events, spans = load(paths[0])
    device_events = {d: e for d, e in device_events.items() if d < n_devices}
    kernels, collectives = classify_hlo(hlo_texts)
    return reduce_events(device_events, spans, kernels, collectives, window_of(spans))
