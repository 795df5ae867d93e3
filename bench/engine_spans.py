"""Device-idle time split by what the engine's host was doing.

The program opens a ``TraceAnnotation`` named ``engine.<phase>`` around
each phase of ``EngineCore.step()``, nested in ``engine.step``
(``ENGINE_SPANS``, from ``serving/metrics.py:StepPhases``); the
benchmark opens ``step`` around each call.  ``split`` divides every
interval of the traced stretch in which no program ran on device 0 among
the innermost of those spans over each part of it: an engine span,
``step`` (inside the benchmark's step span but in no engine span) or
``outside step``.  An idle interval that straddles two spans is divided
where one ends and the other begins, not given whole to the span at its
midpoint.

``read_xplane`` reads a profile as ``trace_reduce.read_xplane`` does,
keeping the engine's spans on the host beside the benchmark's.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce as tr

ENGINE_SPANS = ("engine.step", "engine.schedule", "engine.swap",
                "engine.grow", "engine.cow_replay", "engine.prefill",
                "engine.decode", "engine.guard", "engine.sample",
                "engine.stream", "engine.verify")
STEP = "step"
OUTSIDE = "outside step"
# the phases that wait for the device and turn its logits into tokens,
# and those that prepare and enqueue its work
SAMPLE = ("engine.guard", "engine.sample", "engine.stream")
LAUNCH = ("engine.schedule", "engine.swap", "engine.grow",
          "engine.cow_replay", "engine.prefill", "engine.decode")


def read_xplane(path: str) -> tr.Trace:
    """The profile at ``path`` with the benchmark's and the engine's host
    spans (``trace_reduce.HOST_SPANS`` and ``ENGINE_SPANS``)."""
    from jax.profiler import ProfileData
    names = set(tr.HOST_SPANS) | set(ENGINE_SPANS)
    trace = tr.read_xplane(path)
    host: List[tr.Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name in names)
    host.sort()
    return tr.Trace(trace.devices, host)


def _labelled(host: Iterable[tr.Span], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into segments, each labelled with the innermost
    (shortest) engine span over it, else ``step`` where a step span is
    over it, else ``outside step``."""
    spans = sorted((s, e, n) for s, e, n in host
                   if (n in ENGINE_SPANS or n == STEP) and e > lo and s < hi)
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    active: List[tr.Span] = []
    out: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        engine = [sp for sp in active if sp[2] != STEP]
        if engine:
            label = min(engine, key=lambda sp: sp[1] - sp[0])[2]
        else:
            label = STEP if active else OUTSIDE
        if out and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def split(trace: tr.Trace) -> Optional[Dict[str, float]]:
    """Seconds of device-0 idle in the traced stretch
    (``trace_reduce.window_of``) by label; None for a trace without a
    device.  Where the trace holds any engine span, every engine span is
    a key (0.0 where no idle fell in it); a program without them gives
    only ``step`` and ``outside step``."""
    if not trace.devices:
        return None
    lo, hi = tr.window_of(trace)
    mods = trace.devices[sorted(trace.devices)[0]]["modules"]
    idle = tr.subtract([(lo, hi)], tr.union(mods, lo, hi))
    out: Dict[str, float] = {STEP: 0.0, OUTSIDE: 0.0}
    if any(n in ENGINE_SPANS for _, _, n in trace.host):
        out.update((n, 0.0) for n in ENGINE_SPANS)
    segs = _labelled(trace.host, lo, hi)
    k = 0
    for s, e in idle:
        while segs[k][1] <= s:
            k += 1
        i = k
        while i < len(segs) and segs[i][0] < e:
            a, b, label = segs[i]
            out[label] += (min(b, e) - max(a, s)) / 1e9
            i += 1
    return out


def idle_pct(got: Optional[Dict[str, float]], window_s: float,
             names: Iterable[str]) -> Optional[float]:
    """Percent of a traced stretch of ``window_s`` seconds with device 0
    idle while the host's innermost span is one of ``names``, from a
    ``split``; None where there is no split or its program opened no
    engine span."""
    if not got or not any(n in got for n in ENGINE_SPANS):
        return None
    return 100.0 * sum(got.get(n, 0.0) for n in names) / window_s
