"""Reduction of a profiler trace to device busy time, stage times and the
breakdown.

The JAX profiler writes an ``.xplane.pb`` (an XSpace protobuf).  The parts
read here:

* each ``/device:TPU:<i>`` plane's ``XLA Ops`` line: one event per HLO op
  run, nested (a ``while`` or ``conditional`` event spans its body's ops);
  the op's event metadata holds its ``tf_op`` stat, the ``jax.named_scope``
  path of the op, e.g.
  ``jit(_compress_measure_batch)/vmap(toposzp.stage_detect)/jit(cp_detect)/...``;
* the ``/host:CPU`` plane: ``TraceAnnotation`` spans of the harness
  (``bench.call`` around each timed call) and of the program's
  ``repro.obs`` spans, on the same clock as the device events.

``jax.profiler.ProfileData`` does not expose event metadata stats, so the
few messages needed are decoded here from the protobuf wire format.

Times: busy is the union of the device op intervals inside the traced
window (the first ``bench.call`` start to the last one's end), averaged
over the chips; a stage's time is the union of the intervals of ops whose
scope path names the stage.  Unions, not sums, because events nest.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from bench import spec

CALL_SPAN = "bench.call"


class Op(NamedTuple):
    """One device op run: [start, end) in ns, its trace name and scope."""
    start: float
    end: float
    name: str
    scope: str
    device: int


class Span(NamedTuple):
    """One host span: [start, end) in ns and its name."""
    start: float
    end: float
    name: str


# -- protobuf wire format ---------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _plane(buf, want_stat: Optional[str]):
    """Name, lines and event metadata (name, display name, the string
    value of stat ``want_stat``) of one XPlane."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for num, val in _fields(buf):
        if num == 2:
            name = _str(val)
        elif num == 3:
            lines.append(val)
        elif num == 4:                       # map<int64, XEventMetadata>
            ev_meta_raw = dict(_fields(val))
            ev_meta[ev_meta_raw.get(1, 0)] = ev_meta_raw.get(2)
        elif num == 5:                       # map<int64, XStatMetadata>
            entry = dict(_fields(val))
            sm = dict(_fields(entry.get(2, b"")))
            stat_names[entry.get(1, 0)] = _str(sm.get(2, b""))
    want_id = {v: k for k, v in stat_names.items()}.get(want_stat)
    meta = {}
    for mid, raw in ev_meta.items():
        md_name = md_display = stat = ""
        for num, val in _fields(raw if raw is not None else b""):
            if num == 2:
                md_name = _str(val)
            elif num == 4:
                md_display = _str(val)
            elif num == 5 and want_id is not None:
                st = dict(_fields(val))
                if st.get(1) == want_id and 5 in st:
                    stat = _str(st[5])
        meta[mid] = (md_name, md_display or md_name, stat)
    return name, lines, meta


def _line(buf):
    """Name, base timestamp (ns) and events (metadata id, offset ps,
    duration ps) of one XLine."""
    name, ts, events = "", 0, []
    for num, val in _fields(buf):
        if num == 2:
            name = _str(val)
        elif num == 3:
            ts = _signed(val)
        elif num == 4:
            ev = dict(_fields(val))
            events.append((ev.get(1, 0), _signed(ev.get(2, 0)),
                           _signed(ev.get(3, 0))))
    return name, ts, events


def read_xspace(path: str) -> Tuple[List[Op], List[List[Span]]]:
    """Device ops of every TPU plane, and the host spans, one list per
    host thread."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    ops: List[Op] = []
    threads: List[List[Span]] = []
    for num, pbuf in _fields(data):
        if num != 1:
            continue
        pname = ""
        for n2, v2 in _fields(pbuf):
            if n2 == 2:
                pname = _str(v2)
                break
        m = re.fullmatch(r"/device:TPU:(\d+)", pname)
        if m:
            _, lines, meta = _plane(pbuf, "tf_op")
            for lbuf in lines:
                lname, ts, events = _line(lbuf)
                if lname != "XLA Ops":
                    continue
                for mid, off, dur in events:
                    _, disp, scope = meta.get(mid, ("", "?", ""))
                    s = ts + off / 1000.0
                    ops.append(Op(s, s + dur / 1000.0, disp, scope,
                                  int(m.group(1))))
        elif pname == "/host:CPU":
            _, lines, meta = _plane(pbuf, None)
            for lbuf in lines:
                _, ts, events = _line(lbuf)
                spans = []
                for mid, off, dur in events:
                    s = ts + off / 1000.0
                    spans.append(Span(s, s + dur / 1000.0,
                                      meta.get(mid, ("?",))[0]))
                threads.append(spans)
    return ops, threads


# -- reduction ---------------------------------------------------------------

def merged(intervals) -> List[Tuple[float, float]]:
    """[start, end) intervals merged where they overlap or touch."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def _split_path(scope: str) -> List[str]:
    """Components of a scope path, '/' outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in scope:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def scope_names(scope: str) -> set:
    """Every name on a scope path, with transformation wrappers such as
    ``vmap(...)``, ``jit(...)`` or ``transpose(jvp(...))`` taken off, and
    the op type after ``:`` dropped."""
    names = set()
    for part in _split_path(scope.split(":")[0] if scope.endswith(":")
                            else scope):
        part = part.rstrip(":")
        names.add(part)
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            names.add(part)
            m = _WRAPPED.match(part)
    return names


def in_scope(scope: str, names: Iterable[str]) -> bool:
    have = scope_names(scope)
    return any(n in have for n in names)


class Reduction:
    """Device ops and host spans clipped to the traced window."""

    def __init__(self, ops: List[Op], threads: List[List[Span]]):
        loop = max(threads, key=lambda t: sum(s.name == CALL_SPAN
                                               for s in t), default=[])
        call_spans = sorted(s for s in loop if s.name == CALL_SPAN)
        if not call_spans:
            raise ValueError(f"no {CALL_SPAN!r} span in the trace")
        self.calls = len(call_spans)
        self.t0 = call_spans[0].start
        self.t1 = call_spans[-1].end
        self.host = loop
        self.devices = sorted({o.device for o in ops}) or [0]
        self.ops = [o._replace(start=max(o.start, self.t0),
                               end=min(o.end, self.t1))
                    for o in ops if o.end > self.t0 and o.start < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Union of device op intervals in the window, mean over chips."""
        return sum(union_ns((o.start, o.end) for o in self.ops
                            if o.device == d)
                   for d in self.devices) / len(self.devices) / 1e9

    def scope_s(self, *names: str) -> float:
        """Device seconds (union, mean over chips) of ops whose scope path
        names any of ``names``."""
        return sum(union_ns((o.start, o.end) for o in self.ops
                            if o.device == d and in_scope(o.scope, names))
                   for d in self.devices) / len(self.devices) / 1e9

    def self_times(self) -> Dict[str, float]:
        """Seconds per op name, each event less the time its nested
        events cover, summed over the chips."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            evs = sorted((o for o in self.ops if o.device == d),
                         key=lambda o: (o.start, -o.end))
            stack: List[List] = []        # [op, child-covered ns]

            def close(item):
                op, child = item
                key = op.name + (" @ " + _short_scope(op.scope)
                                 if op.scope else "")
                acc[key] = acc.get(key, 0.0) + max(
                    0.0, (op.end - op.start) - child) / 1e9
            for o in evs:
                while stack and stack[-1][0].end <= o.start:
                    close(stack.pop())
                if stack:
                    stack[-1][1] += o.end - o.start
                stack.append([o, 0.0])
            while stack:
                close(stack.pop())
        return acc

    def top_ops(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in sorted(self.self_times().items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps with no device op in the window, each
        named by the host spans open at its middle (outer to inner)."""
        busy = merged((o.start, o.end) for o in self.ops)
        gaps, t = [], self.t0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.t1:
            gaps.append((t, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            open_ = sorted((sp for sp in self.host
                            if sp.start <= mid < sp.end),
                           key=lambda sp: (sp.start, -sp.end))
            name = " > ".join(sp.name for sp in open_) or "no host span"
            out.append([name[:200], (e - s) / 1e9])
        return out


def _short_scope(scope: str) -> str:
    """A scope path without its leading ``jit(<entry>)`` component."""
    parts = _split_path(scope)
    if len(parts) > 1 and parts[0].startswith("jit("):
        parts = parts[1:]
    return "/".join(parts)[:120]


def reduce_dir(trace_dir: str) -> Reduction:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    ops, threads = read_xspace(paths[0])
    return Reduction(ops, threads)


class Context:
    """What a per-layer metric reader sees: the reduction, the cell and
    the work done in the traced window."""

    def __init__(self, red: Reduction, cell, device_kind: str):
        self.red = red
        self.cell = cell
        self.device_kind = device_kind
        cfg = cell.config
        self.operation = cell.traffic["operation"]
        self.compressor = cfg["compressor"]
        self.n_points = spec.grid_points(cfg)
        self.fields = red.calls * int(cfg["fields_per_call"])

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.red.busy_s / self.red.window_s)

    def ms_per_field(self, *scopes: str) -> Optional[float]:
        """Device ms per field of the ops under ``scopes``; None when the
        trace holds none."""
        s = self.red.scope_s(*scopes)
        return 1000.0 * s / self.fields if s > 0 else None
