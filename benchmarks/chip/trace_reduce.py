"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The window is the host annotation the harness opens around the traced
work (``bench.window``); the profiler puts host and device events on one
clock. From the device planes (``/device:TPU:<n>``), line ``XLA Ops``:

* busy seconds: the union of the op intervals inside the window, per
  device, averaged over the devices;
* per-kernel seconds: the summed durations of the ops whose HLO name
  starts with the kernel's name (a Pallas kernel's custom call takes the
  name of the jitted function that holds it, e.g. ``%lr_sample_pallas.1``);
* the ops that took the most time, by HLO name without its numeric
  suffix;
* idle gaps, each attributed to what the host's Python thread was doing
  at the gap's middle: the innermost ``repro.obs`` span (names such as
  ``chol.panel``), then the innermost runtime event under it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
# repro.obs spans and the harness's own annotations: "layer.name".
SPAN_NAME = re.compile(r"^(bench|chol|serve|trsm|tri_matvec|matvec|round|"
                       r"algebra)\.[a-z_.]+$")
_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the devices
    devices: int
    op_seconds: dict[str, float]        # by HLO name, summed over devices
    idle_gaps: dict[str, float]         # by host activity, device 0
    gap_count: int

    def kernel_seconds(self, prefix: str) -> float:
        """Summed device seconds of the ops named ``prefix`` (with any
        numeric suffix), over all devices, divided by the device count."""
        return sum(s for n, s in self.op_seconds.items()
                   if n == prefix) / max(1, self.devices)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def hlo_name(event_name: str) -> str:
    """``%lr_sample_pallas.1 = f32[...] custom-call(...)`` ->
    ``lr_sample_pallas``; ``fusion.12`` -> ``fusion``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def union_seconds(intervals: list[tuple[int, int]]) -> float:
    """Length of the union of ``[start, end)`` nanosecond intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _gaps(intervals, lo, hi):
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` outside the
    intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _host_lines(pd):
    """Per Python thread of the host, its events as ``(start, end, name)``
    in start order (events of one thread nest)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.append(sorted((e.start_ns, e.end_ns, e.name)
                                  for e in line.events))
    return out


def _attribute(mids, events):
    """For each time in ``mids`` (ascending), what the host thread was
    doing: the innermost span, then the innermost event of any kind under
    it, among the nested ``events`` covering that time."""
    out, stack, i = [], [], 0
    for mid in mids:
        while i < len(events) and events[i][0] <= mid:
            while stack and stack[-1][1] <= events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        spans = [ev for ev in stack if SPAN_NAME.match(ev[2])]
        where = spans[-1][2] if spans else "(no span)"
        if stack and (not spans or stack[-1] is not spans[-1]):
            where += " > " + stack[-1][2][:80]
        out.append(where)
    return out


def reduce_trace(path: str, window: str = "bench.window",
                 gap_min_ns: int = 10_000) -> TraceSummary:
    """Reduce one trace file; ``window`` names the host annotation whose
    extent is the window (the first one found)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines = [ln for ln in _host_lines(pd)
             if any(n == window for _, _, n in ln)]
    if not lines:
        raise ValueError(f"no host event named {window!r} in {path}")
    host = lines[0]
    lo, hi = min((s, e) for s, e, n in host if n == window)
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(f"no device plane in {path}")
    busy, op_seconds, per_dev = [], defaultdict(float), []
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if e <= s:
                    continue
                ivs.append((s, e))
                op_seconds[hlo_name(ev.name)] += (e - s) / 1e9
        busy.append(union_seconds(ivs))
        per_dev.append(ivs)
    gaps = [(s, e) for s, e in _gaps(per_dev[0], lo, hi)
            if e - s >= gap_min_ns]
    idle = defaultdict(float)
    for (s, e), where in zip(gaps, _attribute([(s + e) // 2
                                               for s, e in gaps], host)):
        idle[where] += (e - s) / 1e9
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=sum(busy) / len(busy), devices=len(devices),
                        op_seconds=dict(op_seconds), idle_gaps=dict(idle),
                        gap_count=len(gaps))
