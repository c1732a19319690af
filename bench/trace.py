"""The reduction from a profiler trace to the benchmark's device numbers.

``collect`` reads the ``.xplane.pb`` a traced run wrote and keeps, in a
plain JSON-able form:

- for each TPU device plane, its ``XLA Ops`` events and its ``XLA Modules``
  events, each as ``[name, start_ns, duration_ns, {}]``. An op's name is
  its HLO instruction's text (``%fusion.12 = bf16[...] fusion(...), ...``);
  a module's is the program's name with its fingerprint (``jit_step(123)``);
- the harness's host spans, the ``TraceAnnotation`` events named
  ``bench.*`` (the window and each engine call).

``reduce`` turns that into busy and idle time, time per program, per kind
of op and per backward scope, and the ``breakdown``: the leaf operations
that took most device time and the longest idle gaps, each named by the
harness span the host was in. An op belongs to the module execution whose
interval holds it. Every number is clipped to the window span
``bench.window`` and averaged over the chips the cell uses.

Run ``python bench/trace.py <trace dir>`` to print what a trace holds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
HLO_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')
CONTAINERS = ("while", "call", "conditional", "async-start", "async-done")


def _xplane(logdir) -> str:
    found = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def collect(logdir) -> dict:
    """The trace's device events and harness spans, in plain form."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_xplane(logdir))
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend([e.name, e.start_ns, e.duration_ns, {}]
                                    for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def summary(logdir, samples: int = 12) -> dict:
    """Every plane and line of a trace, with counts and sample events (all
    their stats): for reading a new trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_xplane(logdir))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            lines.append({"line": line.name, "events": len(events),
                          "top_names": names.most_common(samples),
                          "samples": [[e.name, e.start_ns, e.duration_ns,
                                       {k: str(v) for k, v in e.stats}]
                                      for e in events[:samples]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# --------------------------------------------------------------------------- #
def module_name(mod) -> str:
    """A program execution's name without its fingerprint."""
    return re.sub(r"\s*\(\d+\)$", "", str(mod[0]))


def op_name(op) -> str:
    """The HLO instruction's name: ``%fusion.12 = ...`` gives fusion.12."""
    m = INSTRUCTION.match(op[0])
    return m.group(1) if m else op[0]


def is_kernel(op) -> bool:
    """A Pallas (Mosaic) kernel."""
    return bool(KERNEL.search(op[0]))


def op_kind(op) -> str:
    """A short label: ``pallas`` for a Mosaic kernel, else the opcode."""
    if is_kernel(op):
        return "pallas"
    m = OPCODE.search(op[0])
    return m.group(1) if m else re.sub(r"[.][0-9].*$", "", op_name(op))


def is_container(op) -> bool:
    """A loop or call whose body's ops have events of their own."""
    return op_kind(op) in CONTAINERS


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata (the JAX name stack,
    named scopes included), from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]  # ns, on the trace's clock
    devices: Dict[str, dict]
    spans: List[list]

    def __post_init__(self):
        # each op, clipped to the window, with the program that ran it
        lo, hi = self.window
        self._ops, self._mods = {}, {}
        for d, dev in self.devices.items():
            mods = sorted(dev["modules"], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            ops, held = [], collections.defaultdict(list)
            for op in dev["ops"]:
                s, e = max(op[1], lo), min(op[1] + op[2], hi)
                if e <= s:
                    continue
                i = bisect.bisect_right(starts, op[1]) - 1
                inside = i >= 0 and op[1] < mods[i][1] + mods[i][2]
                ops.append((op, s, e, module_name(mods[i]) if inside else ""))
                if inside:
                    held[i].append(op)
            self._ops[d] = ops
            self._mods[d] = [(m, held[i]) for i, m in enumerate(mods)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, dev) -> List[List[float]]:
        return _union((s, e) for _, s, e, _ in self._ops[dev])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(e - s for s, e in self.busy_intervals(d))
               for d in self.devices]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def op_seconds(self, predicate) -> float:
        """Device seconds of the ops ``predicate(op, module)`` selects,
        averaged over the chips. Select leaves only: a container's time
        holds its body's."""
        per = [sum(e - s for op, s, e, mod in self._ops[d]
                   if predicate(op, mod)) for d in self.devices]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def module_seconds(self, predicate) -> float:
        """Device seconds of the program executions ``predicate(name, ops
        inside it)`` selects, averaged over the chips."""
        lo, hi = self.window
        per = []
        for d in self.devices:
            total = 0.0
            for mod, ops in self._mods[d]:
                s, e = max(mod[1], lo), min(mod[1] + mod[2], hi)
                if e > s and predicate(module_name(mod), ops):
                    total += e - s
            per.append(total)
        return sum(per) / len(per) / 1e9 if per else 0.0

    def idle_gaps(self, dev=None) -> List[Tuple[float, float]]:
        dev = dev if dev is not None else sorted(self.devices)[0]
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in self.busy_intervals(dev):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        return gaps

    def host_span_at(self, t: float) -> str:
        """The innermost harness span the host was in at time ``t``."""
        best = None
        for name, s, d in self.spans:
            if name != WINDOW_SPAN and s <= t <= s + d:
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else "between engine calls"

    def breakdown(self, top: int = 10) -> dict:
        """The leaf operations that took most device time, by program and
        kind, and the longest idle gaps, by the span the host was in."""
        dev = sorted(self.devices)[0]
        by_op: Dict[str, float] = collections.defaultdict(float)
        for op, s, e, mod in self._ops[dev]:
            if not is_container(op):
                by_op[f"{mod or '?'}:{op_kind(op)}"] += e - s
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(dev), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[self.host_span_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps]}


def reduce(events: dict, device_ids: Sequence[int]) -> Reduction:
    """The window's reduction over the devices the cell uses."""
    window = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError("the trace has no bench.window span")
    _, start, dur = window[-1]
    wanted = {str(i) for i in device_ids}
    devices = {k: v for k, v in events["devices"].items() if k in wanted}
    if not devices:
        raise ValueError(f"the trace has none of the devices {sorted(wanted)}"
                         f" (found {sorted(events['devices'])})")
    return Reduction((start, start + dur), devices, events["spans"])


if __name__ == "__main__":
    json.dump(summary(sys.argv[1]), sys.stdout, indent=1, default=str)
