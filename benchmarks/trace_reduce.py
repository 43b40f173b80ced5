"""From the profiler's trace (``*.xplane.pb``) to the numbers the metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU's
plane (``/device:TPU:<n>``) carries a line ``XLA Ops`` with one event per
executed HLO op and a line ``XLA Modules`` with one event per executed
program; host planes carry the ``TraceMe`` spans (``TraceAnnotation`` and
JAX's own) by thread. All share one clock, in nanoseconds.

    busy_s          union of the op intervals, averaged over the device planes
    window_s        the traced window: from the first to the last event of
                    the host's ``bench/`` spans (else of the device's ops)
    device_ops      [[name, seconds], ...] most expensive first. An event's
                    name is the whole HLO instruction; it is cut to the
                    instruction's name without its number, with the fusion
                    kind (``reshape``, ``fusion[kOutput]``), and summed over
                    all instructions and executions that share it
    idle_gaps       [[what the host was doing, seconds], ...] the longest
                    gaps between op intervals of the first plane, each named
                    after the host span that covers most of it
    step_executions executions of the step program (``XLA Modules`` events
                    whose name starts with ``step_program``), per plane
    step_busy_s     union of op intervals inside those executions
    mxu_s           seconds, inside those executions, of the events that hold
                    a convolution or a dot (``is_mxu``)

``python3 benchmarks/trace_reduce.py FILE [--dump]`` prints the reduction,
or with ``--dump`` what the file holds, to be read by hand.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_KIND = re.compile(r"kind=(k\w+)")
_NUMBER = re.compile(r"\.\d+$")


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), kind=kOutput, calls=..`` ->
    ``fusion[kOutput]``."""
    name = _NUMBER.sub("", hlo.split(" = ", 1)[0].lstrip("%"))
    kind = _KIND.search(hlo)
    return f"{name}[{kind.group(1)}]" if kind else name


def is_mxu(hlo: str) -> bool:
    """Whether the event holds a convolution or a dot. ``ProfileData`` does
    not expose the profiler's ``hlo_category``, so the selection reads the
    event's own HLO text: on a TPU a convolution or dot with the ops fused
    onto its output is a fusion of ``kind=kOutput`` (read by hand in the
    first traces of PR 25: every ``convolution_*_fusion`` is one, and the
    weight-gradient products that carry Adam's update are too), and an
    unfused one is named after its opcode. Pallas kernels are
    ``custom-call`` and never counted."""
    name = hlo.split(" = ", 1)[0]
    return ("kind=kOutput" in hlo or name.startswith(("%convolution", "%dot"))
            or "convolution" in name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:       # noqa: BLE001 - a stat the binding cannot decode
        return {}


def _device_planes(data) -> list:
    return [p for p in data.planes
            if p.name.startswith("/device:") and any(
                l.name == OPS_LINE for l in p.lines)]


def _line(plane, name: str):
    return next((l for l in plane.lines if l.name == name), None)


def _host_spans(data) -> list:
    """(start_ns, end_ns, name) of every span on a host plane."""
    spans = []
    for p in data.planes:
        if p.name.startswith("/device:"):
            continue
        for l in p.lines:
            for e in l.events:
                spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return spans


def _name_gap(spans: list, lo: float, hi: float) -> str:
    """What the host was doing in the gap: the runtime's or JAX's span that
    covers most of it (the shortest among equals, so the innermost). Where
    none covers half, the host was in Python between runtime calls, inside
    the benchmark's own ``bench/`` span that is named."""
    best, key = "", (0.0, 0.0)
    outer, outer_cov = "outside the benchmark's calls", 0.0
    for s, e, name in spans:
        cov = (min(e, hi) - max(s, lo)) / (hi - lo)
        if cov <= 0:
            continue
        if name.startswith("bench/"):
            if cov > outer_cov:
                outer, outer_cov = name, cov
        elif (round(cov, 2), -(e - s)) > key:
            best, key = name, (round(cov, 2), -(e - s))
    return best if key[0] >= 0.5 else f"{outer}: Python between runtime calls"


def load(xplane: str):
    """The file as ``ProfileData``; a ``.gz`` (the recorded test trace) is
    unpacked in memory."""
    from jax.profiler import ProfileData

    if xplane.endswith(".gz"):
        import gzip

        with gzip.open(xplane, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(xplane)


def reduce(xplane: str, step_program: str | None = None,
           on_device: bool = True) -> dict:
    data = load(xplane)
    spans = _host_spans(data)
    bench = [(s, e) for s, e, n in spans if n.startswith("bench/")]
    planes = _device_planes(data)
    if not planes:
        if on_device:
            raise RuntimeError("the trace holds no device plane with an "
                               f"{OPS_LINE!r} line: nothing ran on the device")
        return {"busy_s": None, "window_s": None, "device_ops": [],
                "idle_gaps": [], "host_spans": len(spans)}
    n = len(planes)
    busy = step_busy = mxu = 0.0
    executions = 0
    by_name: dict = {}
    gaps = []
    lo = min(s for s, _ in bench) if bench else None
    hi = max(e for _, e in bench) if bench else None
    for k, plane in enumerate(planes):
        events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in _line(plane, OPS_LINE).events)
        if lo is None:
            lo, hi = events[0][0], max(e for _, e, _ in events)
        modules = _line(plane, MODULES_LINE)
        runs = union([(m.start_ns, m.start_ns + m.duration_ns)
                      for m in (modules.events if modules else ())
                      if step_program and m.name.startswith(step_program)
                      and lo <= m.start_ns
                      and m.start_ns + m.duration_ns <= hi])
        executions += len(runs)
        shorts: dict = {}       # few distinct instructions, many events
        i, end, step_end = 0, lo, lo    # one sweep: unions by running ends
        for s, e, name in events:
            if s < lo or e > hi:
                continue
            if name not in shorts:
                shorts[name] = (short_name(name), is_mxu(name))
            short, on_mxu = shorts[name]
            by_name[short] = by_name.get(short, 0.0) + (e - s)
            if s > end and k == 0:
                gaps.append((s - end, end, s))
            busy += max(0.0, e - max(s, end))
            end = max(end, e)
            while i < len(runs) and runs[i][1] <= s:
                i += 1
            if i < len(runs) and runs[i][0] <= s:
                step_busy += max(0.0, e - max(s, step_end))
                step_end = max(step_end, e)
                if on_mxu:
                    mxu += e - s
        if k == 0 and hi > end:
            gaps.append((hi - end, end, hi))
    gaps.sort(reverse=True)
    ns = 1e-9
    return {
        "busy_s": busy / n * ns,
        "window_s": (hi - lo) * ns,
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_name_gap(spans, a, b), g * ns]
                      for g, a, b in gaps[:5]],
        "step_executions": executions / n,
        "step_busy_s": step_busy / n * ns,
        "mxu_s": mxu / n * ns,
        "planes": n,
    }


def dump(xplane: str, limit: int = 12) -> None:
    """What the file holds: planes, lines, the first events with their
    stats. For reading one trace by hand before trusting the reduction."""
    data = load(xplane)
    for p in data.planes:
        print(f"plane {p.name!r}")
        for l in p.lines:
            events = list(l.events)
            print(f"  line {l.name!r}: {len(events)} events")
            names: dict = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            print(f"    names: {top}")
            for e in events[:3]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns}"
                      f" stats {_stats(e)}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    else:
        print(json.dumps(reduce(sys.argv[1],
                                *(sys.argv[2:3] or [None])), indent=1))
