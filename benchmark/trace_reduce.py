"""From a profiler trace to numbers: the one reduction.

`load(path)` reads an .xplane.pb with benchmark/xplane.py into plain
tuples; every function below works on those tuples, so a test can
hand-build them. Times are seconds.

What the device planes hold on jax 0.9 / libtpu 0.0.34 (PERF.md section 5):
line `XLA Modules` (one event per executable run), line `XLA Ops` (the
per-op timeline: busy time is the union of these), `Async XLA Ops`
(overlapping copies: not added). An op event's name is the whole HLO
instruction text, so the instruction's own name is cut from its front;
its stats carry `tf_op` (the op_name path with the framework's
`{type}:{block}/{idx}` scope) and `hlo_category`.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
TEXT_CUT = 600
SHORT_GAP = 20e-6   # idle gaps under this are summed, not attributed
SCOPE = re.compile(r"([A-Za-z_][\w:]*?):(\d+)/(\d+)")


class Op(NamedTuple):
    name: str       # the HLO instruction's name, e.g. fusion.2300
    tf_op: str      # op_name path, '' where the profiler gave none
    category: str   # hlo_category, '' where none
    start: float
    dur: float
    text: str = ""  # the HLO instruction's text (shapes and all), cut


class Span(NamedTuple):
    name: str
    start: float
    dur: float


class Trace(NamedTuple):
    ops: list        # one list of Op a device
    modules: list    # one list of Span a device
    host: list       # Span, every host thread together


def instruction_name(text):
    """`%fusion.2 = f32[...] fusion(...)` -> `fusion.2`."""
    head = text.split(" = ", 1)[0].strip()
    return head.lstrip("%") or text[:64]


def load(path):
    """The device planes' ops and executable runs and the host's spans.
    Read with benchmark/xplane.py: an op's `tf_op` and `hlo_category`
    are stats of its metadata, which jax.profiler.ProfileData does not
    show."""
    from benchmark import xplane
    ops, modules, host = [], [], []
    for plane in xplane.read_planes(path):
        name = plane["name"]
        if name.startswith(("/device:TPU:", "/device:GPU:")):
            dev_ops, dev_mods = [], []
            for line in plane["lines"]:
                if line["name"] == "XLA Ops":
                    for text, disp, start, dur, st in line["events"]:
                        dev_ops.append(Op(
                            disp or instruction_name(text),
                            str(st.get("tf_op", "")),
                            str(st.get("hlo_category", "")),
                            start, dur, text[:TEXT_CUT]))
                elif line["name"] == "XLA Modules":
                    dev_mods += [Span(disp or text, start, dur)
                                 for text, disp, start, dur, _ in
                                 line["events"]]
            ops.append(sorted(dev_ops, key=lambda o: o.start))
            modules.append(sorted(dev_mods, key=lambda s: s.start))
        elif name == "/host:CPU":
            for line in plane["lines"]:
                host += [Span(text, start, dur)
                         for text, _, start, dur, _ in line["events"]]
    return Trace(ops, modules, sorted(host, key=lambda s: s.start))


def step_modules(dev_modules, pattern="^jit_step"):
    """The runs of the program's executables: every Executor.run also
    puts a `jit_convert_element_type` run (its step counter) on the
    device, which is not a step."""
    rx = re.compile(pattern)
    return [m for m in dev_modules if rx.search(m.name)]


# -- intervals ------------------------------------------------------------

def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def busy_seconds(dev_ops):
    return total(union((o.start, o.start + o.dur) for o in dev_ops))


def mean_busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    return sum(busy_seconds(d) for d in trace.ops) / len(trace.ops)


def window_seconds(trace):
    """The traced window as the device saw it: first module start to
    last module end, over all devices."""
    spans = [s for d in trace.modules for s in step_modules(d)] or \
        [Span("", o.start, o.dur) for d in trace.ops for o in d]
    if not spans:
        return 0.0
    return max(s.start + s.dur for s in spans) - min(s.start for s in spans)


# -- per scope, per category ---------------------------------------------

def scope_seconds(dev_ops, pattern):
    """Device time of the ops whose `tf_op` matches `pattern` (a
    regular expression, searched)."""
    rx = re.compile(pattern)
    return sum(o.dur for o in dev_ops if rx.search(o.tf_op))


def category_seconds(dev_ops, pattern):
    rx = re.compile(pattern)
    return sum(o.dur for o in dev_ops if rx.search(o.category))


def ops_within(dev_ops, spans):
    """The ops that start inside one of `spans`."""
    out, i = [], 0
    spans = sorted(spans, key=lambda s: s.start)
    for o in dev_ops:
        while i < len(spans) and spans[i].start + spans[i].dur <= o.start:
            i += 1
        if i < len(spans) and spans[i].start <= o.start:
            out.append(o)
    return out


def module_gaps(dev_modules):
    """Idle time between consecutive executable runs on one device."""
    gaps = []
    for a, b in zip(dev_modules, dev_modules[1:]):
        gaps.append(max(0.0, b.start - (a.start + a.dur)))
    return gaps


def exposed_collective_seconds(dev_ops):
    """Time of collective ops during which no other op runs on that
    device: the part of communication that compute does not hide."""
    coll = union((o.start, o.start + o.dur) for o in dev_ops
                 if COLLECTIVE.match(o.name))
    comp = union((o.start, o.start + o.dur) for o in dev_ops
                 if not COLLECTIVE.match(o.name))
    hidden, j = 0.0, 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            hidden += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
    return total(coll) - hidden


# -- the breakdown that goes into the ledger --------------------------------

def scope_of(tf_op):
    m = SCOPE.search(tf_op)
    return f"{m.group(1)}:{m.group(2)}/{m.group(3)}" if m else ""


def top_ops(trace, n=10):
    """The device ops that took most time (first device), by
    instruction name with its framework scope: [[name, seconds], ...]."""
    if not trace.ops:
        return []
    acc = defaultdict(float)
    for o in trace.ops[0]:
        scope = scope_of(o.tf_op)
        acc[f"{o.name}__{scope}" if scope else o.name] += o.dur
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, n=10, ignore=("$", "Thread")):
    """The idle time of the first device by what the host was doing:
    every gap between busy intervals goes to the shortest host span
    that covers its middle."""
    if not trace.ops:
        return []
    busy = union((o.start, o.start + o.dur) for o in trace.ops[0])
    host = [s for s in trace.host if not s.name.startswith(ignore)]
    acc = defaultdict(float)
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 - e0 < SHORT_GAP:
            acc["_shorter_gaps_"] += s1 - e0
            continue
        mid = (e0 + s1) / 2
        cover = [s for s in host if s.start <= mid < s.start + s.dur]
        name = min(cover, key=lambda s: s.dur).name if cover \
            else "unattributed"
        acc[re.sub(r"[^\w.\-]", "_", name)[:60]] += s1 - e0
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]
