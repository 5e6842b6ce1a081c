"""From a profiler trace to the device's busy time, its top operations and
its idle gaps, each gap labelled by what the host was doing.

``load`` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData``: the
operations of every device (the ``XLA Ops`` line of each ``/device:TPU:n``
plane, each named by its HLO op and the program of the ``XLA Modules``
line that runs it) and the host spans the benchmark sets itself
(``TraceAnnotation``s whose names start with ``bench.``).  Device and host
events share one clock in the file.  ``reduce`` works on plain lists, so it
is tested on constructed events as well as on a recorded trace.

Definitions, over the stretch ``[lo, hi)`` (the ``bench.traced`` span):

* busy: the length of the union of one device's operation intervals,
  averaged over the devices;
* idle share: ``1 - busy / (hi - lo)``;
* top operations: total clipped time per operation name (``module:op``);
* idle gaps: the stretches of the first device with no operation running,
  each labelled by the innermost ``bench.`` host span that covers its middle
  (``host: outside engine calls`` where none does), summed per label.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]          # name, start_ns, end_ns

BENCH_PREFIX = "bench."
STRETCH = "bench.traced"
NO_SPAN = "host: outside engine calls"


def is_tpu_ops(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def is_cpu_ops(plane: str, line: str) -> bool:
    """XLA's CPU backend runs its operations on host threads (tests only);
    there an operation is an event that names its HLO op."""
    return plane == "/host:CPU" and line.startswith("tf_XLA")


DEVICE_LINES = {"tpu": is_tpu_ops, "cpu": is_cpu_ops}
MODULES = "XLA Modules"


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_superstep(8911407554505906894)`` -> ``jit_superstep``."""
    return text.split("(", 1)[0]


def load(path: Path, device_lines: Callable[[str, str], bool] = is_tpu_ops
         ) -> Tuple[Dict[str, List[Interval]], List[Interval]]:
    """(operations per device plane, named ``module:op``; bench host
    spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        lines = list(plane.lines)
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       module_name(ev.name))
                      for ln in lines if ln.name == MODULES
                      for ev in ln.events)
        for line in lines:
            if device_lines(plane.name, line.name):
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    stats = dict(ev.stats)
                    if plane.name.startswith("/host:") and \
                            "hlo_op" not in stats:
                        continue
                    mod = stats.get("hlo_module") or _covering(
                        mods, ev.start_ns)
                    name = op_name(ev.name)
                    ops.append((f"{mod}:{name}" if mod else name,
                                ev.start_ns, ev.start_ns + ev.duration_ns))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(BENCH_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return devices, host


def _covering(mods: list, t: float) -> Optional[str]:
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2]
    return None


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ops: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops
            if b > lo and a < hi]


def stretch_of(host: List[Interval]) -> Optional[Tuple[float, float]]:
    spans = [(a, b) for n, a, b in host if n == STRETCH]
    return max(spans, key=lambda s: s[1] - s[0]) if spans else None


def label_at(host: List[Interval], t: float) -> str:
    covering = [(b - a, n) for n, a, b in host
                if a <= t < b and n != STRETCH]
    return min(covering)[1] if covering else NO_SPAN


def reduce(devices: Dict[str, List[Interval]], host: List[Interval],
           lo: float, hi: float, top: int = 10) -> dict:
    """Busy seconds, window seconds, top operations and labelled idle
    gaps of the stretch ``[lo, hi)`` (nanoseconds)."""
    if not devices:
        raise ValueError("the trace holds no device operations")
    if hi <= lo:
        raise ValueError(f"empty stretch [{lo}, {hi})")
    busy = []
    per_op: Dict[str, float] = defaultdict(float)
    first = sorted(devices)[0]
    first_union: List[Tuple[float, float]] = []
    for plane in sorted(devices):
        ops = _clip(devices[plane], lo, hi)
        u = union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in u))
        if plane == first:
            first_union = u
            for n, a, b in ops:
                per_op[n] += b - a
    gaps, edge = [], lo
    for a, b in first_union + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    by_label: Dict[str, float] = defaultdict(float)
    longest = []
    for a, b in gaps:
        lab = label_at(host, (a + b) / 2)
        by_label[lab] += b - a
        longest.append((lab, (b - a) * 1e-9))
    busy_s = sum(busy) / len(busy) * 1e-9
    window_s = (hi - lo) * 1e-9
    rank = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t * 1e-9] for n, t in rank],
        "idle_gaps": [[n, t * 1e-9] for n, t in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": sorted(longest, key=lambda g: -g[1])[:top],
        "gap_count": len(gaps),
    }


def reduce_file(path: Path, device_lines=is_tpu_ops) -> dict:
    devices, host = load(path, device_lines)
    st = stretch_of(host)
    if st is None:
        raise ValueError(f"no {STRETCH} span in {path}")
    return reduce(devices, host, *st)
