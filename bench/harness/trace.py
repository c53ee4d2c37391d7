"""Reduce a profiler capture of the measured window to device operations
tagged by layer, and the sums the per-layer metrics read.

An operation's layer comes from the ``jax.named_scope`` path that the
program gave it, read from the HLO module embedded in the capture:

- ``exchange``: under a ``tier_pull_*`` or ``refresh_ring_*`` scope, or a
  collective-permute (the halo rings between chips);
- ``dense`` / ``aggregation``: under a ``layer<i>`` scope, split by the
  operation's HLO category: matmul (convolution) fusions are the dense
  transform, every other operation there the aggregation.

The window is the host span ``bench/window`` that the harness opens around
the timed epochs; operations are clipped to it.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from .xspace import hlo_op_names, read_xspace

WINDOW_SPAN = "bench/window"
_LAYER = re.compile(r"(?:^|[/(])layer\d+(?:[/)]|$)")
_EXCHANGE = re.compile(r"(?:^|[/(])(?:tier_pull_|refresh_ring_)")
_U64 = (1 << 64) - 1


@dataclasses.dataclass
class Op:
    start: float          # ns, capture clock
    end: float
    name: str             # named-scope path (or the HLO name without one)
    opcode: str
    category: str         # the profiler's HLO category
    tags: frozenset
    hlo: str = ""         # the HLO instruction's name


@dataclasses.dataclass
class Reduced:
    window: tuple         # (start_ns, end_ns)
    devices: list         # per device: list[Op] of its compute stream
    host: list            # host spans: (start, end, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def tags_of(op_name: str, opcode: str, category: str) -> frozenset:
    tags = set()
    base = opcode.removesuffix("-start").removesuffix("-done")
    if _EXCHANGE.search(op_name) or base == "collective-permute":
        tags.add("exchange")
    elif _LAYER.search(op_name):
        tags.add("dense" if "convolution" in category else "aggregation")
    return frozenset(tags)


def union_s(intervals) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
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


def gaps(intervals, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` outside the union."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _hlo_names(planes) -> dict:
    names = {}
    for pl in planes:
        if pl["name"] != "/host:metadata":
            continue
        for key, em in pl["event_metadata"].items():
            proto = em["stats"].get("Hlo Proto")
            if proto:
                names[key & _U64] = hlo_op_names(proto)
    return names


def _line(plane, name):
    return next((ln["events"] for ln in plane["lines"]
                 if ln["name"] == name), [])


def reduce_capture(path) -> Reduced:
    planes = read_xspace(path)
    hlo = _hlo_names(planes)
    host_planes = [p for p in planes if p["name"] == "/host:CPU"]
    host = []
    for pl in host_planes:
        meta = pl["event_metadata"]
        for ln in pl["lines"]:
            for ev in ln["events"]:
                host.append((ev["start_ns"], ev["start_ns"] + ev["dur_ns"],
                             meta[ev["metadata_id"]]["name"], ev["stats"]))
    spans = [(s, e) for s, e, n, _ in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the capture has no {WINDOW_SPAN!r} host span")
    lo, hi = spans[-1]

    def op_of(ev, meta, hlo_name):
        stats = dict(meta["stats"])
        stats.update(ev["stats"])
        pid = stats.get("program_id")
        opcode, op_name = (hlo.get(pid, {}).get(hlo_name, ("", ""))
                           if pid is not None else ("", ""))
        cat = str(stats.get("hlo_category", ""))
        s = max(ev["start_ns"], lo)
        e = min(ev["start_ns"] + ev["dur_ns"], hi)
        return Op(s, e, op_name or hlo_name, opcode, cat,
                  tags_of(op_name, opcode, cat), hlo_name)

    devices = []
    dev_planes = sorted((p for p in planes
                         if re.fullmatch(r"/device:TPU:\d+", p["name"])),
                        key=lambda p: int(p["name"].rsplit(":", 1)[1]))
    for pl in dev_planes:
        meta = pl["event_metadata"]
        ops = []
        for ev in _line(pl, "XLA Ops"):
            if ev["start_ns"] + ev["dur_ns"] <= lo or ev["start_ns"] >= hi:
                continue
            m = meta[ev["metadata_id"]]
            ops.append(op_of(ev, m, m["display_name"] or m["name"]))
        devices.append([o for o in ops if o.end > o.start])
    if not dev_planes:
        # the CPU backend logs its operations on host threads; a rehearsal
        # reduces them so that the code path runs, and reports no device
        # figure from them
        ops = []
        for s, e, n, st in host:
            if "hlo_op" in st and e > lo and s < hi:
                pid = st.get("program_id")
                opcode, op_name = hlo.get(pid, {}).get(n, ("", ""))
                ops.append(Op(max(s, lo), min(e, hi), op_name or n, opcode,
                              "", tags_of(op_name, opcode, ""), n))
        devices.append(ops)
    host_spans = [(max(s, lo), min(e, hi), n) for s, e, n, st in host
                  if "hlo_op" not in st and e > lo and s < hi and e > s]
    return Reduced(window=(lo, hi), devices=devices, host=host_spans)


def tagged_s(red: Reduced, tag: str) -> float:
    """Device seconds of operations tagged ``tag``, mean over devices."""
    return sum(union_s([(o.start, o.end) for o in ops if tag in o.tags])
               for ops in red.devices) / len(red.devices)


def busy_s(red: Reduced) -> float:
    """Seconds in which some operation ran, mean over devices."""
    return sum(union_s([(o.start, o.end) for o in ops])
               for ops in red.devices) / len(red.devices)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time (by named-scope path,
    seconds summed over the window, mean over devices), and the idle time
    of the first device by what the host was doing: each idle stretch goes
    to the innermost host span that covers its middle."""
    by_name = defaultdict(float)
    for ops in red.devices:
        for o in ops:
            by_name[_short(o.name)] += (o.end - o.start) / 1e9
    n_dev = len(red.devices)
    device_ops = sorted(([k, v / n_dev] for k, v in by_name.items()),
                        key=lambda kv: -kv[1])[:top]
    idle = defaultdict(float)
    busy = [(o.start, o.end) for o in red.devices[0]]
    host = sorted(red.host)
    for s, e in gaps(busy, *red.window):
        mid = (s + e) / 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        label = (min(cover, key=lambda h: h[1] - h[0])[2] if cover
                 else "no host span")
        idle[label] += (e - s) / 1e9
    idle_gaps = sorted(([k, v] for k, v in idle.items() if v > 0),
                       key=lambda kv: -kv[1])[:top]
    return {"device_ops": device_ops, "idle_gaps": idle_gaps}


def _short(name: str) -> str:
    return name.removeprefix("jit(step)/")[:120]
