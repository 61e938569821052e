"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read through ``jax.profiler.ProfileData``.  Device planes are those named
``/device:<kind>:<n>`` other than the host's; on each, the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules``
one per executed program.  Event times are nanoseconds from the start
of the trace; the host plane's ``bench.clock_sync`` annotation, stamped
at a known ``perf_counter`` reading, places the program's own spans on
the same clock.

The window is [sync mark, sync mark + window_s]: every device number is
clipped to it and averaged over the chips the cell uses.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"send|recv|allreduce|allgather", re.I)


OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
# ops whose interval holds other ops of the same line (a scan's loop)
CONTAINERS = ("while", "conditional", "call")


class Op:
    """One device event.  On a TPU an ``XLA Ops`` event is named by its
    HLO instruction text, ``%name = type opcode(operands), attributes``."""
    __slots__ = ("name", "start", "end", "chip")

    def __init__(self, name, start, end, chip):
        self.name, self.start, self.end, self.chip = name, start, end, chip

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """The instruction's name without its numeric suffix."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"[.\d]+$", "", head) or head

    @property
    def opcode(self) -> str:
        if " = " not in self.name:
            return ""
        m = OPCODE_RE.search(self.name.split(" = ", 1)[1])
        return m.group(1) if m else ""

    @property
    def container(self) -> bool:
        return self.opcode in CONTAINERS


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """a minus b; both sorted and disjoint (as ``union`` gives them)."""
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


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


class Reduced:
    """One trace, reduced to the window."""

    def __init__(self, ops: List[Op], modules: List[Op], chips: int,
                 window_s: float, host: List[Tuple]):
        self.ops = ops
        self.modules = modules
        self.chips = chips
        self.window_s = window_s
        self.host = host
        per_chip = defaultdict(list)
        for op in ops:
            per_chip[op.chip].append((op.start, op.end))
        self.busy_by_chip = {c: union(iv) for c, iv in per_chip.items()}
        self.busy_s = (sum(length(iv) for iv in self.busy_by_chip.values())
                       / max(chips, 1))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, rule: Callable[[Op], bool]) -> float:
        """Device seconds of the ops the rule picks, averaged over chips
        (each chip's picked intervals merged, so nothing counts twice)."""
        per_chip = defaultdict(list)
        for op in self.ops:
            if rule(op):
                per_chip[op.chip].append((op.start, op.end))
        return (sum(length(union(iv)) for iv in per_chip.values())
                / max(self.chips, 1))

    def picked(self, rule: Callable[[Op], bool]) -> List[Op]:
        return [op for op in self.ops if rule(op)]

    def exposed_collective_s(self) -> float:
        """Time in which a collective runs on a chip and no other op does,
        averaged over the chips."""
        total = 0.0
        for chip in self.busy_by_chip:
            mine = [o for o in self.ops if o.chip == chip
                    and not o.container]
            coll = union([(o.start, o.end) for o in mine
                          if COLLECTIVE_RE.search(o.opcode)])
            comp = union([(o.start, o.end) for o in mine
                          if not COLLECTIVE_RE.search(o.opcode)])
            total += length(subtract(coll, comp))
        return total / max(self.chips, 1)

    def module_time(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches, averaged
        over chips."""
        rx = re.compile(pattern)
        per_chip = defaultdict(list)
        for m in self.modules:
            if rx.search(m.name):
                per_chip[m.chip].append((m.start, m.end))
        return (sum(length(union(iv)) for iv in per_chip.values())
                / max(self.chips, 1))

    def breakdown(self, top: int = 10) -> Dict:
        """The device ops that took most time (by name without its numeric
        suffix, averaged over chips), and the longest idle gaps on the
        first chip, each named by the host span that covers most of it."""
        by_name: Dict[str, float] = defaultdict(float)
        for op in self.ops:
            if not op.container:
                by_name[f"{op.short} ({op.opcode or 'op'})"] += op.dur
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        device_ops = [[n, t / max(self.chips, 1)] for n, t in ops]
        gaps: List[Interval] = []
        if self.busy_by_chip:
            chip = min(self.busy_by_chip)
            gaps = subtract([(0.0, self.window_s)], self.busy_by_chip[chip])
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": device_ops,
                "idle_gaps": [[self._host_label(g), g[1] - g[0]]
                              for g in gaps]}

    def _host_label(self, gap: Interval) -> str:
        """The innermost span of the thread that drives the program that
        covers most of the gap; else the best-covering span of another
        thread."""
        best, key = "no program span", (0.0, 0.0)
        for name, s, d, main in self.host:
            c = min(gap[1], s + d) - max(gap[0], s)
            if c <= 0:
                continue
            k = (float(main), c / (gap[1] - gap[0]) - 1e-9 * d)
            if k > key:
                best, key = (name if main else f"{name} (other thread)"), k
        return best


def reduce(path: str, window_s: float, chips: int,
           host_spans: Sequence[Tuple]) -> Reduced:
    """Load the trace and clip it to the window (seconds from its start).

    ``host_spans`` are (name, start, duration, on the driving thread),
    in seconds from the window's start, as ``common.spans_in`` gives
    them."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    sync = None
    device_planes = []
    for plane in prof.planes:
        if _is_device_plane(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.clock_sync":
                    sync = ev.start_ns
    if sync is None:
        raise SystemExit("bench: the trace holds no bench.clock_sync mark")
    lo = sync * 1e-9
    hi = lo + window_s
    ops: List[Op] = []
    modules: List[Op] = []
    chip_ids = {}
    for plane in device_planes:
        m = re.search(r"(\d+)$", plane.name)
        chip = int(m.group(1)) if m else len(chip_ids)
        chip_ids[plane.name] = chip
        for line in plane.lines:
            sink = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
            if sink is None:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    sink.append(Op(ev.name, s - lo, e - lo, chip))
    used = sorted(set(chip_ids.values()))[:chips]
    ops = [o for o in ops if o.chip in used]
    modules = [o for o in modules if o.chip in used]
    return Reduced(ops, modules, len(used) or chips, window_s,
                   list(host_spans))
