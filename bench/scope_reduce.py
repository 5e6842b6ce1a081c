"""From a profiler trace to the program's own account of the device's time:
self time per device scope, and idle gaps labelled by the program's host
spans.

The program names its device work with ``jax.named_scope``
(``repro.core.scopes``): the names ride every HLO operation's ``op_name``
metadata (``jit(superstep)/while/body/draft/...``).  Its host spans are
``jax.profiler.TraceAnnotation``s whose names start with ``dvi.``
(``dvi.tick.harvest``, ``dvi.driver.idle``).  ``load`` reads both from a
``.xplane.pb``; ``reduce`` works on plain lists, so it is tested on
constructed events as well as on a recorded trace.

Definitions, over the stretch ``[lo, hi)`` (the ``bench.traced`` span) of the
first device:

* self time: at every instant the device is busy, the operation that began
  last among those running takes the instant.  A ``while`` loop's event
  spans its body's operations, so the loop keeps only the time none of them
  runs, and the self times add up to the busy time;
* scope of an operation: the innermost scope name on its ``op_name`` path
  (a fusion carries the path of its root instruction); where there is
  none, its program (``jit_superstep``); where no program is known,
  ``other``.  The names are ``SCOPES``, those every configuration's program
  has, plus the ``SCOPES`` of the configuration's model module, which an
  architecture that names device work of its own adds;
* program gaps: the stretches with no operation running, each labelled by
  the innermost ``dvi.`` host span that covers its middle (``NO_SPAN`` where
  none does), summed per label.  The profiler records a span only if it
  began inside the capture, and loses the last spans of a thread still
  running when the capture stops.  The program's tracer keeps every phase,
  each naming its annotation, and its clock anchor places them on the
  capture's clock (``tracer_spans``): they stand in where the capture lost
  a span.  A gap before the first or after the last span is labelled
  ``EDGE``: what the host did there is in neither.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import trace_reduce

# the scope names of every configuration's program (repro.core.scopes.ALL,
# less what a model module adds), kept here as data: the benchmark imports
# nothing of the program
SCOPES = ("draft", "verify", "commit", "learn.log", "learn.update",
          "prefill.chunk", "prefill.admit")
PROGRAM_PREFIX = "dvi."
OTHER = "other"
NO_SPAN = "host: no program span"
EDGE = "host: outside the recorded program spans"
REST = "rest"

Op = Tuple[float, float, str]                # start_ns, end_ns, label


def scope_of(path: str, scopes: Tuple[str, ...] = SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path (``tf_op`` adds
    ``:<type>``); a transformation's wrapper counts as what it wraps
    (``transpose(jvp(learn.update))``)."""
    for part in reversed(path.rsplit(":", 1)[0].split("/")):
        name = part.rstrip(")").rsplit("(", 1)[-1]
        if name in scopes:
            return name
    return None


def label(paths: Dict[Tuple[int, str], str], module: Optional[str],
          name: str, scopes: Tuple[str, ...] = SCOPES) -> str:
    """An operation's label: its scope, else its program, else ``OTHER``
    (``paths`` from ``op_paths``; ``module`` as the ``XLA Modules`` line or
    the ``hlo_module`` stat names it)."""
    if module is None:
        return OTHER
    return scope_of(paths.get((program_id(module), name), ""), scopes) or \
        trace_reduce.module_name(module)


# --- the few protobuf fields of an XSpace that ProfileData does not show:
# an operation's ``tf_op`` (its op_name path) is a stat of its event
# *metadata*, one per (program, operation), on the device plane

def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is its (start, end) in ``buf``."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wt} at byte {i}")
        yield num, wt, val


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_paths(raw: bytes, is_device: Callable[[str], bool]
             ) -> Dict[Tuple[int, str], str]:
    """``{(program id, event name): tf_op}`` of the device planes (XSpace
    field 1 planes; XPlane 2 name, 4 event_metadata, 5 stat_metadata;
    XEventMetadata 2 name, 5 stats; XStat 1 metadata_id, 3 uint64, 4
    int64, 5 str, 7 ref)."""
    out: Dict[Tuple[int, str], str] = {}
    for num, _, plane in _fields(raw):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, _, v in _fields(raw, *plane):
            if f == 2:
                name = _text(raw, v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                for kf, _, kv in _fields(raw, *v):
                    if kf == 2:
                        sm = {sf: sv for sf, _, sv in _fields(raw, *kv)}
                        stat_names[sm.get(1, 0)] = _text(raw, sm[2]) \
                            if 2 in sm else ""
        if not is_device(name):
            continue
        for entry in metas:
            em = next((v for f, _, v in _fields(raw, *entry) if f == 2),
                      None)
            if em is None:
                continue
            ev_name, stats = "", {}
            for f, _, v in _fields(raw, *em):
                if f == 2:
                    ev_name = _text(raw, v)
                elif f == 5:
                    st = {sf: sv for sf, _, sv in _fields(raw, *v)}
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = _text(raw, st[5])
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    elif 3 in st or 4 in st:
                        stats[key] = st.get(3, st.get(4))
            if "tf_op" in stats:
                out[(int(stats.get("program_id", 0)), ev_name)] = \
                    stats["tf_op"]
    return out


def program_id(module_event: str) -> int:
    """``jit_superstep(8911407554505906894)`` -> 8911407554505906894."""
    inner = module_event.rsplit("(", 1)[-1].rstrip(")")
    return int(inner) if inner.isdigit() else 0


def tracer_spans(trace: dict, start_ns: int) -> List[tuple]:
    """The phase spans of the program's tracer (``Tracer.to_dict()``) as
    host spans ``(annotation, start, end)`` on the clock of a capture that
    began at ``start_ns`` (wall clock): each ``X`` event that names its
    annotation, placed through the tracer's clock anchor."""
    anchor = trace.get("otherData", {}).get("clock_anchor")
    if anchor is None:
        return []
    at = anchor["wall_ns"] - start_ns
    return [(e["args"]["annotation"], at + e["ts"] * 1e3,
             at + (e["ts"] + e["dur"]) * 1e3)
            for e in trace["traceEvents"]
            if e["ph"] == "X" and "annotation" in e.get("args", {})]


def load(path: Path, device_lines: Callable[[str, str], bool]
         = trace_reduce.is_tpu_ops, scopes: Tuple[str, ...] = SCOPES
         ) -> Tuple[List[Op], List[tuple], int]:
    """(the first device's operations, each labelled by ``scopes``; the
    stretch span and the program's host spans; the capture's start,
    wall-clock nanoseconds)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    paths = op_paths(raw, lambda plane: plane.startswith("/device:"))
    data = ProfileData.from_serialized_xspace(raw)
    planes: Dict[str, List[Op]] = {}
    host: List[tuple] = []
    start = 0
    for plane in data.planes:
        start = int(dict(plane.stats).get("profile_start_time", start))
        lines = list(plane.lines)
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ln in lines if ln.name == trace_reduce.MODULES
                      for ev in ln.events)
        for line in lines:
            if device_lines(plane.name, line.name):
                ops = planes.setdefault(plane.name, [])
                for ev in line.events:
                    stats = dict(ev.stats)
                    if plane.name.startswith("/host:") and \
                            "hlo_op" not in stats:
                        continue
                    mod = stats.get("hlo_module") or trace_reduce._covering(
                        mods, ev.start_ns)
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                label(paths, mod, ev.name, scopes)))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX) or \
                            ev.name == trace_reduce.STRETCH:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    if not planes:
        raise ValueError(f"the trace {path} holds no device operations")
    return planes[sorted(planes)[0]], host, start


def self_times(ops: List[Op], lo: float, hi: float
               ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """(self nanoseconds, operation count) per label over ``[lo, hi)``."""
    clipped = sorted(((max(a, lo), min(b, hi), n) for a, b, n in ops
                      if b > lo and a < hi), key=lambda o: (o[0], -o[1]))
    secs: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    running: list = []              # (-order, end, label): last begun on top
    t = lo

    def advance(until: float) -> None:
        nonlocal t
        while running and t < until:
            _, end, name = running[0]
            if end <= t:
                heapq.heappop(running)
                continue
            stop = min(end, until)
            secs[name] += stop - t
            t = stop
        t = max(t, until)

    for i, (a, b, name) in enumerate(clipped):
        advance(a)
        heapq.heappush(running, (-i, b, name))
        count[name] += 1
    advance(hi)
    return dict(secs), dict(count)


def reduce(ops: List[Op], host: List[tuple], lo: float, hi: float) -> dict:
    """Seconds per scope (with ``other``) and per program gap label, over
    the stretch ``[lo, hi)`` (nanoseconds)."""
    if hi <= lo:
        raise ValueError(f"empty stretch [{lo}, {hi})")
    secs, count = self_times(ops, lo, hi)
    busy = trace_reduce.union([(max(a, lo), min(b, hi)) for a, b, _ in ops
                               if b > lo and a < hi])
    spans = [(n, a, b) for n, a, b in host if n.startswith(PROGRAM_PREFIX)]
    first = min((a for _, a, _ in spans), default=lo)
    last = max((b for _, _, b in spans), default=hi)
    gaps: Dict[str, float] = defaultdict(float)
    edge = lo
    for a, b in busy + [(hi, hi)]:
        if a > edge:
            mid = (a + edge) / 2
            found = trace_reduce.label_at(spans, mid)
            if found == trace_reduce.NO_SPAN:
                found = NO_SPAN if first <= mid < last else EDGE
            gaps[found] += a - edge
        edge = max(edge, b)
    secs.setdefault(OTHER, 0.0)

    def ranked(d: dict) -> dict:
        return {k: v * 1e-9 for k, v in
                sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))}

    return {"scope_busy_s": sum(b - a for a, b in busy) * 1e-9,
            "scopes": ranked(secs), "scope_ops": count,
            "program_gaps": ranked(gaps)}


def reduce_file(path: Path, device_lines=trace_reduce.is_tpu_ops,
                trace: Optional[dict] = None,
                scopes: Tuple[str, ...] = SCOPES) -> dict:
    """``reduce`` over a ``.xplane.pb``; ``trace``: the program tracer's
    ``to_dict()``, whose phase spans stand in for lost annotations;
    ``scopes``: the scope names of the configuration's program."""
    ops, host, start = load(path, device_lines, scopes)
    st = trace_reduce.stretch_of(host)
    if st is None:
        raise ValueError(f"no {trace_reduce.STRETCH} span in {path}")
    if trace is not None:
        host = host + tracer_spans(trace, start)
    return reduce(ops, host, *st)


def top(secs: Dict[str, float], n: int = 10) -> List[list]:
    """The largest ``n`` entries of ``secs`` as ``[name, seconds]`` pairs,
    largest first; past ``n``, the last pair is ``REST`` and holds the sum
    of the smaller ones, so the list keeps the whole's sum (a breakdown
    list has at most 10 entries)."""
    items = sorted(secs.items(), key=lambda kv: (-kv[1], kv[0]))
    if len(items) > n:
        items = items[:n - 1] + [(REST, sum(v for _, v in items[n - 1:]))]
    return [[k, v] for k, v in items]


def share(run: dict, metric: str, scopes: Tuple[str, ...]) -> Optional[float]:
    """A metric reader's share (%) of the device's busy time in ``scopes``;
    None where the run holds no scope split."""
    tr = run["trace"]
    if tr is None or "scopes" not in tr or not tr["scope_busy_s"]:
        return None
    run.setdefault("samples", {})[metric] = sum(
        tr["scope_ops"].get(s, 0) for s in scopes)
    return 100.0 * sum(tr["scopes"].get(s, 0.0) for s in scopes) / tr[
        "scope_busy_s"]
