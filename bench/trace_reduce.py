"""Reduce a profiler trace (`.xplane.pb`) to device time, per program and
in all, and to the device's idle gaps put down to what the host was doing.

Read with `jax.profiler.ProfileData`, nothing else.  The traced window is
the host span `bench.client` that the harness opens when it starts the
profiler and closes before it stops it.  On each device plane
(`/device:TPU:<n>`), the `XLA Ops` line holds every op the device ran and
the `XLA Modules` line every execution of a compiled program:

- busy: the union of op intervals inside the window, averaged over the
  devices that ran anything;
- module times: the duration of each program execution, by program name
  (`jit_decode_step(...)` and the like);
- device ops: the time of the innermost ops (a `while` that holds the scan
  over layers is not counted beside the ops inside it), summed by program
  and instruction, `jit_decode_step/fusion.12 bf16[16,4096] fusion`, the
  largest ten;
- idle gaps: the window minus busy, each gap put down to the deepest host
  event on the client's thread that covers its middle (`bench.schedule`,
  a jitted call's dispatch, ...), summed by that name, the largest ten.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

WINDOW_SPAN = "bench.client"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict            # program name -> [seconds per execution]
    device_ops: list         # [[op name, seconds], ...]
    idle_gaps: list          # [[host activity, seconds], ...]
    devices: int

    def module_times(self, prefix: str) -> list:
        """Durations of every execution of the programs named `prefix...`."""
        return [t for name, ts in self.modules.items()
                if name.startswith(prefix) for t in ts]


def union(intervals: list) -> list:
    """Merge [start, end] intervals; returns them sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that `busy` (disjoint, sorted) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(hlo_text: str) -> str:
    """`%fusion.12 = bf16[16,4096]{1,0:T(8,128)} fusion(...), ...` ->
    `fusion.12 bf16[16,4096] fusion`; a tuple result reads `(...)`."""
    lhs, _, rhs = hlo_text.partition(" = ")
    if not rhs:
        return hlo_text[:80]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = "(...)", rhs[i + 1:]
    else:
        shape, _, rest = rhs.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    return f"{lhs.lstrip('%')} {_LAYOUT.sub('', shape)} {opcode}"


def _program(name: str) -> str:
    """`jit_decode_step(1234)` -> `jit_decode_step`."""
    return name.split("(", 1)[0]


def _leaves(ops: list) -> list:
    """The ops that hold no other op inside their interval."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, e) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][1] < e and ops[i + 1][2] <= e:
            continue
        out.append((n, s, e))
    return out


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]


def _window(host_planes) -> Optional[tuple]:
    for plane in host_planes:
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW_SPAN:
                    return s, e, line
    return None


def _attribute(mids: list, host: list) -> list:
    """For each instant of `mids` (ascending), the deepest host event of
    `host` [(start, end, name)] that covers it.  Events on one thread nest,
    so a stack of the open events holds the answer at its top."""
    host = sorted(host, key=lambda h: (h[0], -h[1]))
    out, stack, j = [], [], 0
    for m in mids:
        while j < len(host) and host[j][0] <= m:
            while stack and stack[-1][1] < host[j][0]:
                stack.pop()
            stack.append(host[j])
            j += 1
        while stack and stack[-1][1] < m:
            stack.pop()
        out.append(stack[-1][2] if stack else "(no host event)")
    return out


def summarize_profile(pd) -> Summary:
    planes = list(pd.planes)
    host_planes = [p for p in planes if p.name.startswith("/host:")]
    dev_planes = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    found = _window(host_planes)
    if found is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi, client_line = found
    busy_ns, op_time, modules, all_busy = [], {}, {}, []
    for plane in dev_planes:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = [(n, s, e) for n, s, e in _events(lines[OPS_LINE]) if e > lo and s < hi]
        if not ops:
            continue
        busy = union(clip([[s, e] for _, s, e in ops], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        all_busy.append(busy)
        mods = sorted(_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for n, s, e in mods:
            if s >= lo and e <= hi:
                modules.setdefault(n, []).append((e - s) / 1e9)
        for n, s, e in _leaves(ops):
            k = bisect.bisect_right(starts, s) - 1
            prog = _program(mods[k][0]) if k >= 0 and s < mods[k][2] else "?"
            key = f"{prog}/{op_label(n)}"
            op_time[key] = op_time.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    if not busy_ns:
        raise ValueError("no device ran an op inside the traced window")
    # idle gaps: instants at which no device was busy
    free = gaps(union([iv for b in all_busy for iv in b]), lo, hi)
    host = [(s, e, n) for n, s, e in _events(client_line) if e > lo and s < hi]
    idle: dict = {}
    for (s, e), name in zip(free, _attribute([(s + e) / 2 for s, e in free], host)):
        idle[name] = idle.get(name, 0.0) + (e - s) / 1e9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / len(busy_ns) / 1e9,
        modules=modules,
        device_ops=[[n, t] for n, t in top],
        idle_gaps=[[n, t] for n, t in top_idle],
        devices=len(busy_ns),
    )


def summarize(path: str) -> Summary:
    from jax.profiler import ProfileData

    return summarize_profile(ProfileData.from_file(path))
