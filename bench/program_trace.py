"""The program's own side of a traced run: the spans ``repro.obs`` writes
into the profiler's trace, each device op's JAX name stack, and the splits
of device time that read them.

An enabled ``repro.obs`` tracer writes each of its spans as a host event
named for the span and marked with the ``obs_cat`` stat (the span's
category), on the profiler's clock. A TPU op event's metadata carries its
HLO instruction's JAX name stack, named scopes included, as the ``tf_op``
stat. ``collect`` keeps, beside all that ``trace.collect`` keeps:

- ``program_spans``: ``[name, start_ns, duration_ns, {stat: value}]`` for
  every marked host event;
- in each device op's fourth field, ``{"scope": <name stack>}`` where the
  event has one.

``idle_split`` divides each chip's idle time in the window exactly by the
innermost program span open on the host; ``scope_seconds`` sums one
program's leaf ops by named scope.

A per-layer reader reaches these through ``reduction_for(ctx)``. The
harness's reduction drops event stats, so the traced run's events are read
again from its trace directory, which is still on disk while the readers
run, and matched to the reduction by its window.

Run ``python -m bench.program_trace <trace dir>`` from the checkout to
print both splits.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace as tr

MARK = "obs_cat"  # repro.obs.trace.PROFILER_MARK
SCOPE_STAT = "tf_op"
BURST = "jit_burst"  # the continuous engine's decode burst
LAYER_SCOPES = ("mixer", "mlp", "head")  # models/lm.py's named scopes


def collect(logdir) -> dict:
    """``trace.collect``'s events, each op's name stack in its fourth field,
    and the program's marked host spans under ``program_spans``."""
    from jax.profiler import ProfileData

    path = tr._xplane(logdir)
    stacks = op_name_stacks(path)
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    program: List[list] = []
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    named = iter(stacks[m.group(1)])
                    for e in line.events:
                        name, stack = next(named)
                        if name != e.name:
                            raise ValueError(f"op {e.name!r} is {name!r} "
                                             "in the trace's metadata")
                        dev["ops"].append([e.name, e.start_ns, e.duration_ns,
                                           {"scope": stack} if stack else {}])
                elif line.name == tr.MODULES_LINE:
                    dev["modules"].extend([e.name, e.start_ns, e.duration_ns,
                                           {}] for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tr.SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                        continue
                    stats = dict(e.stats)
                    if MARK in stats:
                        program.append([e.name, e.start_ns, e.duration_ns,
                                        {k: str(v) for k, v in stats.items()}])
    return {"devices": devices, "spans": spans, "program_spans": program}


def op_name_stacks(path) -> Dict[str, List[Tuple[str, str]]]:
    """``(name, name stack)`` of each TPU plane's ``XLA Ops`` events, in
    event order. The name stack is the ``tf_op`` stat of the event's
    metadata, which ``jax.profiler.ProfileData`` does not show, so the file
    is read a second time, as the ``XSpace`` message it is."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat = {e.key for e in plane.stat_metadata
                if e.value.name == SCOPE_STAT}
        meta = {e.key: (e.value.name, next(
            (s.str_value for s in e.value.stats if s.metadata_id in stat),
            "")) for e in plane.event_metadata}
        out[m.group(1)] = [meta[e.metadata_id] for line in plane.lines
                           if line.name == tr.OPS_LINE for e in line.events]
    return out


@functools.lru_cache(maxsize=None)
def _xspace():
    """The message class of the profiler's ``XSpace`` file (tsl's
    ``xplane.proto``), with only the fields read here; the parser skips the
    rest."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    field = descriptor_pb2.FieldDescriptorProto
    one, many = field.LABEL_OPTIONAL, field.LABEL_REPEATED
    text, num = {"type": field.TYPE_STRING}, {"type": field.TYPE_INT64}

    def of(name):
        return {"type": field.TYPE_MESSAGE, "type_name": f".xplane.{name}"}

    messages = {  # field number -> (name, label, type)
        "XStat": {1: ("metadata_id", one, num), 5: ("str_value", one, text)},
        "XEvent": {1: ("metadata_id", one, num)},
        "XLine": {2: ("name", one, text), 4: ("events", many, of("XEvent"))},
        "XEventMetadata": {2: ("name", one, text),
                           5: ("stats", many, of("XStat"))},
        "XStatMetadata": {2: ("name", one, text)},
        # a map<int64, V> field is a repeated {1: key, 2: value} message
        "EventMetadataEntry": {1: ("key", one, num),
                               2: ("value", one, of("XEventMetadata"))},
        "StatMetadataEntry": {1: ("key", one, num),
                              2: ("value", one, of("XStatMetadata"))},
        "XPlane": {2: ("name", one, text), 3: ("lines", many, of("XLine")),
                   4: ("event_metadata", many, of("EventMetadataEntry")),
                   5: ("stat_metadata", many, of("StatMetadataEntry"))},
        "XSpace": {1: ("planes", many, of("XPlane"))},
    }
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="xplane", syntax="proto3")
    for name, fields in messages.items():
        msg = proto.message_type.add(name=name)
        for number, (fname, label, kind) in fields.items():
            msg.field.add(name=fname, number=number, label=label, **kind)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xplane.XSpace"))


@dataclasses.dataclass
class ProgramReduction(tr.Reduction):
    """A reduction whose ops carry their name stacks, with the program's
    spans beside the harness's."""

    program_spans: List[list] = dataclasses.field(default_factory=list)


def reduce(events: dict, device_ids) -> ProgramReduction:
    """``trace.reduce``, with the program's spans kept."""
    red = tr.reduce(events, device_ids)
    return ProgramReduction(red.window, red.devices, red.spans,
                            events.get("program_spans", []))


def reduction_for(ctx) -> Optional[ProgramReduction]:
    """The reader's reduction with each op's name stack and the program's
    spans; None where the traced run's events cannot be found again."""
    red = ctx.trace
    if isinstance(red, ProgramReduction):
        return red
    return _find(ctx.cell.get("name", ""), tuple(red.window),
                 tuple(red.devices))


@functools.lru_cache(maxsize=1)  # the readers of one run share one read
def _find(cell_name: str, window: tuple,
          devices: tuple) -> Optional[ProgramReduction]:
    """The traced run's events on disk whose window is ``window``."""
    from bench import harness

    for logdir in sorted(harness.OUT_DIR.glob(f"trace-{cell_name}-*"),
                         key=lambda p: -p.stat().st_mtime):
        try:
            events = collect(logdir)
        except FileNotFoundError:
            continue
        found = [s for s in events["spans"] if s[0] == tr.WINDOW_SPAN]
        if found and (found[-1][1], found[-1][1] + found[-1][2]) == window:
            return reduce(events, list(devices))
    return None


# --------------------------------------------------------------------------- #
def innermost_segments(spans, lo: float, hi: float) -> List[tuple]:
    """``[(start, end, span or None)]`` covering ``[lo, hi)`` in order: the
    innermost span open throughout each piece (the latest started; of two
    started together, the shorter), None where none is open."""
    spans = sorted(spans, key=lambda s: s[1])
    points = sorted({lo, hi} | {t for s in spans for t in (s[1], s[1] + s[2])
                                if lo < t < hi})
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][1] <= a:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] + s[2] > a]
        inner = max(active, key=lambda s: (s[1], -s[2]), default=None)
        out.append((a, b, inner))
    return out


def layer_of(span) -> str:
    """The program layer an idle interval belongs to, by the innermost span
    open: the rollout engine's host code, the DAG's stage code (``node/*``,
    ``prompts/next``, ``worker/balance``, ...), or none (between
    iterations)."""
    if span is None:
        return "outside_spans"
    return "rollout_host" if span[3].get(MARK) == "rollout" else "dag_host"


def idle_split(red: ProgramReduction, key=layer_of) -> Dict[str, float]:
    """Seconds of idle device time in the window by ``key(innermost program
    span open on the host)``, averaged over the chips. Each idle interval is
    cut at span boundaries, so the parts sum to the idle time."""
    lo, hi = red.window
    segs = innermost_segments(red.program_spans, lo, hi)
    starts = [s[0] for s in segs]
    total: Dict[str, float] = collections.defaultdict(float)
    for dev in red.devices:
        for g0, g1 in red.idle_gaps(dev):
            j = max(bisect.bisect_right(starts, g0) - 1, 0)
            while j < len(segs) and segs[j][0] < g1:
                a, b, span = segs[j]
                cut = min(g1, b) - max(g0, a)
                if cut > 0:
                    total[key(span)] += cut
                j += 1
    n = len(red.devices)
    return {k: v / n / 1e9 for k, v in total.items()}


def _in_scope(scope: str):
    """Matches a name stack that holds the named scope ``scope``, forward or
    inside a transform such as ``transpose(jvp(scope))``."""
    return re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)").search


def scope_seconds(red: tr.Reduction, program: str,
                  scope: str) -> Optional[float]:
    """Device seconds of ``program``'s leaf ops under the named scope
    ``scope``, averaged over the chips; None where no op of the program
    carries any of the model's layer scopes (a program built without
    them)."""
    leaves = [(op, mod) for d in red.devices for op, _, _, mod in red._ops[d]
              if mod == program and not tr.is_container(op)]
    named = [_in_scope(s) for s in LAYER_SCOPES]
    if not any(m(op[3].get("scope", "")) for op, _ in leaves for m in named):
        return None
    inside = _in_scope(scope)
    return red.op_seconds(lambda op, mod: mod == program
                          and not tr.is_container(op)
                          and bool(inside(op[3].get("scope", ""))))


def burst_share(ctx, scope: str) -> Optional[float]:
    """A reader's share of the window in the decode burst's ops under
    ``scope``."""
    red = reduction_for(ctx)
    t = None if red is None else scope_seconds(red, BURST, scope)
    return None if t is None else t / ctx.window_s


if __name__ == "__main__":
    events = collect(sys.argv[1])
    red = reduce(events, sorted(events["devices"])[:1])
    w = red.window_s
    json.dump({
        "window_s": w, "device_idle_share": 1 - red.busy_s / w,
        "idle_share": {k: v / w for k, v in idle_split(red).items()},
        "idle_by_span": {k: v / w for k, v in idle_split(
            red, key=lambda s: s[0] if s else None).items()},
        "burst_share": {s: (scope_seconds(red, BURST, s) or 0) / w
                        for s in LAYER_SCOPES},
        "program_spans": collections.Counter(
            s[0] for s in red.program_spans).most_common()},
        sys.stdout, indent=1)
