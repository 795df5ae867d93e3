"""Reduce a profiler trace to device busy time, kernel times, exposed
collectives and idle gaps labelled by what the host was doing.

The input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (``read_xplane``), or the same events as a
plain dict (``Trace``), which is what the tests record.  On a TPU every
device plane (``/device:TPU:<n>``) has an ``XLA Modules`` line, one
event per program run, and an ``XLA Ops`` line, one event per HLO
instruction run (a ``while`` loop's event spans its body's events).  The
host plane holds the benchmark's own ``TraceAnnotation`` spans, whose
names are in ``HOST_SPANS``.  All times are nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

Span = Tuple[float, float, str]          # (start_ns, end_ns, name)

# spans the benchmark's host code opens around what it calls
HOST_SPANS = ("window", "step", "submit", "wait", "drain")
# HLO instructions whose event spans other instructions' events
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")


@dataclasses.dataclass
class Trace:
    # device plane name -> {"modules": [Span], "ops": [Span]}
    devices: Dict[str, Dict[str, List[Span]]]
    host: List[Span]

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        return cls({k: {line: [tuple(e) for e in evs]
                        for line, evs in v.items()}
                    for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Span]]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Modules": "modules", "XLA Ops": "ops"}
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = lines.get(line.name)
                if key is None:
                    continue
                dev[key].extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name in HOST_SPANS)
    for dev in devices.values():
        dev["modules"].sort()
        dev["ops"].sort()
    host.sort()
    return Trace(devices, host)


def short_name(op: str) -> str:
    """``%paged_flash_decode_fwd.9 = bf16[...] custom-call(...)`` ->
    ``paged_flash_decode_fwd.9``; a module ``jit_dec(1179...)`` ->
    ``jit_dec``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return head.split("(", 1)[0]


def op_kind(op: str) -> str:
    """The instruction's name without its number: ``copy.113`` ->
    ``copy``."""
    return re.sub(r"\.\d+$", "", short_name(op))


def union(spans: Iterable[Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Disjoint intervals covering ``spans`` clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e, _ in sorted(spans):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[str, float]                 # per device
    kernel_s: Dict[str, float]               # kernel prefix -> seconds, all devices
    kernel_calls: Dict[str, int]
    collective_exposed_s: Dict[str, float]   # per device
    step_s: float                            # host step spans in the window
    steps: int
    top_ops: List[Tuple[str, float]]         # program/instruction, all devices
    idle_gaps: List[Tuple[str, float]]       # longest gaps, device 0
    idle_by_host: Dict[str, float]           # gap seconds by host span, device 0

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(1, len(self.busy_s))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s / self.window_s


def window_of(trace: Trace) -> Tuple[float, float]:
    """The ``window`` host span, else the extent of the step spans."""
    for s, e, n in trace.host:
        if n == "window":
            return s, e
    steps = [(s, e) for s, e, n in trace.host if n == "step"]
    if not steps:
        raise ValueError("trace holds no window and no step span")
    return steps[0][0], steps[-1][1]


def _host_label(host: List[Span], t: float) -> str:
    """The innermost (shortest) benchmark span covering ``t``."""
    best = None
    for s, e, n in host:
        if n != "window" and s <= t < e and (best is None
                                              or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def reduce_trace(trace: Trace, kernels: Iterable[str] = (),
                 n_top: int = 10) -> Reduced:
    lo, hi = window_of(trace)
    busy, exposed = {}, {}
    kernel_s = collections.Counter()
    kernel_calls = collections.Counter()
    ops_s = collections.Counter()
    gaps: List[Tuple[str, float]] = []
    idle_by_host = collections.Counter()
    kernels = tuple(kernels)
    for di, dev in enumerate(sorted(trace.devices)):
        lines = trace.devices[dev]
        mods = lines["modules"]
        starts = [m[0] for m in mods]
        busy_iv = union(mods, lo, hi)
        busy[dev] = length(busy_iv) / 1e9
        leaf, coll = [], []
        for s, e, n in lines["ops"]:
            if not (lo <= s < hi):
                continue
            kind = op_kind(n)
            if kind in CONTAINERS:
                continue
            sn = short_name(n)
            for k in kernels:
                if sn.startswith(k):
                    kernel_s[k] += (min(e, hi) - s) / 1e9
                    kernel_calls[k] += 1
            (coll if kind.startswith(COLLECTIVES) else leaf).append((s, e, n))
            i = bisect.bisect_right(starts, s) - 1
            mod = short_name(mods[i][2]) if i >= 0 and s < mods[i][1] \
                else "?"
            ops_s[f"{mod}/{sn}"] += (min(e, hi) - s) / 1e9
        exposed[dev] = length(subtract(union(coll, lo, hi),
                                       union(leaf, lo, hi))) / 1e9
        if di == 0:
            idle = subtract([(lo, hi)], busy_iv)
            for s, e in idle:
                label = _host_label(trace.host, (s + e) / 2)
                gaps.append((label, (e - s) / 1e9))
                idle_by_host[label] += (e - s) / 1e9
    steps = [(s, e) for s, e, n in trace.host
             if n == "step" and lo <= s < hi]
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy, kernel_s=dict(kernel_s),
        kernel_calls=dict(kernel_calls), collective_exposed_s=exposed,
        step_s=sum(min(e, hi) - s for s, e in steps) / 1e9,
        steps=len(steps), top_ops=ops_s.most_common(n_top),
        idle_gaps=gaps[:n_top], idle_by_host=dict(idle_by_host))


def reduce_file(path: str, kernels: Iterable[str] = ()) -> Reduced:
    return reduce_trace(read_xplane(path), kernels)


def find_xplane(log_dir: str) -> Optional[str]:
    import glob
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    return files[-1] if files else None
