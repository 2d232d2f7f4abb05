"""What the program's named scopes and host spans add to ``bench/trace.py``'s
reduction of a profiler trace, read from the raw ``XSpace`` in one parse.

``jax.profiler.ProfileData`` leaves out each device operation's metadata;
the raw proto holds its ``tf_op`` (the ``jax.named_scope`` path of the op,
e.g. ``jit(chunk)/while/body/closed_call/vmap(local_steps)/...``) and its
``program_id``. :func:`load` parses the file once into planes, lines and
events with ``ProfileData``'s fields, so ``trace.reduce`` reads the same
parse, and :func:`reduce` adds to its summary only what it lacks:

- ``clock_shift_ns``: the least shift of the device's clock onto the host's
  that puts every program's device start after the host event that
  launched it began (``fed/dispatch`` where the launch lies in one,
  otherwise the outermost ``PjitFunction(<fn>)`` event); never negative;
- ``idle_gaps``, named anew by :func:`idle_gaps`: each idle gap, after that
  shift, named by the innermost host span open at its midpoint. Where spans
  do not nest and the shift is 0 it names every gap as ``trace.reduce``
  does, so it can take that function's place;
- ``scopes``: the device seconds of the round chunk's leaf operations
  (not ``while``, ``conditional`` or ``call``, whose events span their
  bodies), each credited to the innermost of ``SCOPES`` in its ``tf_op``
  path, or to ``unscoped``; averaged over the devices; and
  ``unscoped_ops``, the unscoped operations that took most time.

:func:`self_times` and :func:`train_layers` turn these and the program's
span records (``repro.utils.spans.record_to``) into per-layer numbers.
The generated ``xplane_pb2`` is loaded by its file path, so TensorFlow is
never imported.
"""
from __future__ import annotations

import bisect
import collections
import functools
import importlib.util
import os
import re

from bench import trace

PROGRAM = "jit_chunk"
SCOPES = ("loss_pass", "local_steps", "ghost_pull", "merge")
NON_LEAF = frozenset({"while", "conditional", "call"})
PROGRAM_SPAN = "fed/dispatch"
_LAUNCH = re.compile(r"PjitFunction\((.*)\)")

Event = collections.namedtuple("Event",
                               "name start_ns end_ns duration_ns metadata_id")


@functools.lru_cache(maxsize=1)
def xplane_pb2():
    """The generated ``tsl/profiler/protobuf/xplane_pb2.py`` of the
    installed TensorFlow package, loaded by path."""
    spec = importlib.util.find_spec("tensorflow")
    path = None if spec is None else os.path.join(
        spec.submodule_search_locations[0], "tsl", "profiler", "protobuf",
        "xplane_pb2.py")
    if path is None or not os.path.exists(path):
        raise RuntimeError("bench: no generated xplane_pb2.py to read the "
                           "trace's operation metadata with")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Line:
    def __init__(self, proto, names: dict):
        self.name = proto.name
        self._proto, self._names = proto, names

    @property
    def events(self):
        """Times as ``ProfileData`` gives them: whole nanoseconds of the
        line's start plus the event's offset, and of its duration."""
        base = self._proto.timestamp_ns
        for ev in self._proto.events:
            start = float(base + ev.offset_ps // 1000)
            dur = float(ev.duration_ps // 1000)
            yield Event(self._names[ev.metadata_id], start, start + dur, dur,
                        ev.metadata_id)


class Plane:
    def __init__(self, proto):
        self.name = proto.name
        self._proto = proto
        names = {k: md.name for k, md in proto.event_metadata.items()}
        self.lines = [Line(ln, names) for ln in proto.lines]
        self._stat_names = {k: v.name for k, v in proto.stat_metadata.items()}
        self._ops: dict = {}

    def _value(self, stat):
        kind = stat.WhichOneof("value")
        if kind == "ref_value":
            return self._stat_names.get(stat.ref_value)
        return getattr(stat, kind) if kind else None

    def op(self, mid: int) -> tuple:
        """(opcode, ``tf_op`` path, program id) of an operation's metadata."""
        if mid not in self._ops:
            md = self._proto.event_metadata[mid]
            stats = {self._stat_names.get(s.metadata_id): self._value(s)
                     for s in md.stats}
            program = stats.get("program_id")
            self._ops[mid] = (_opcode(md.name), str(stats.get("tf_op") or ""),
                              None if program is None else int(program))
        return self._ops[mid]


class Space:
    """The planes of one ``XSpace``, shaped as ``ProfileData``'s."""

    def __init__(self, proto):
        self.planes = [Plane(p) for p in proto.planes]


def load(path: str) -> Space:
    proto = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        proto.ParseFromString(f.read())
    return Space(proto)


def _opcode(text: str) -> str:
    """The opcode of an HLO instruction's text (``%x = shape opcode(...)``)."""
    rhs = text.split(" = ", 1)[-1]
    if rhs.startswith("("):                 # a tuple shape
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.partition(" ")[2]
    return rhs.strip().split("(", 1)[0]


def _scope(tf_op: str) -> str:
    """The innermost of ``SCOPES`` in an op's path; transformations wrap a
    scope's name (``vmap(local_steps)``, ``transpose(jvp(merge))``)."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        name = part.split(":", 1)[0]
        while (m := re.fullmatch(r"[\w-]+\((.*)\)", name)):
            name = m.group(1)
        if name in SCOPES:
            return name
    return "unscoped"


def _by_start(ev):
    """Start order, an enclosing event before those it encloses."""
    return ev.start_ns, -ev.end_ns


def _host_events(space):
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield line, sorted(line.events, key=_by_start)


def clock_shift_ns(space) -> float:
    """See the module docstring. Launches and executions are paired in
    order, per program, where their counts agree."""
    launches: dict = {}
    for _, events in _host_events(space):
        dispatch = [(e.start_ns, e.end_ns) for e in events
                    if e.name == PROGRAM_SPAN]
        starts = [s for s, _ in dispatch]
        outer_end: dict = {}
        for ev in events:
            m = _LAUNCH.fullmatch(ev.name)
            if m is None or ev.start_ns < outer_end.get(ev.name, -1.0):
                continue
            outer_end[ev.name] = ev.end_ns
            t = ev.start_ns
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and dispatch[i][1] >= t:
                t = dispatch[i][0]
            launches.setdefault(_key("jit_" + m.group(1)), []).append(t)
    planes = trace._device_planes(space)
    mod_line = trace._line(planes[0], "XLA Modules") if planes else None
    execs: dict = {}
    for ev in (mod_line.events if mod_line is not None else ()):
        execs.setdefault(_key(trace._module_name(ev.name)), []).append(
            ev.start_ns)
    shift = 0.0
    for k, devs in execs.items():
        hosts = sorted(launches.get(k, ()))
        if len(hosts) == len(devs):
            shift = max([shift] + [h - d for h, d in zip(hosts, sorted(devs))])
    return shift


def _key(name: str) -> str:
    """A program's name as launch and module both spell it
    (``PjitFunction(<lambda>)`` runs ``jit__lambda``)."""
    return re.sub(r"[^0-9A-Za-z]", "", name)


def idle_gaps(space, shift_ns: float, span_names) -> list:
    """[[span name, idle seconds]] of the ``trace.TOP`` names that hold
    most, largest first: each gap between the first device's busy
    intervals, its midpoint moved ``shift_ns`` onto the host's clock, is
    named by the innermost span of ``span_names`` open there (the latest
    to start), or ``other``."""
    names = set(span_names)
    spans = sorted((e.start_ns, e.end_ns, e.name)
                   for _, events in _host_events(space) for e in events
                   if e.name in names)
    planes = trace._device_planes(space)
    merged = []
    if planes:
        line = (trace._line(planes[0], "XLA Ops")
                or trace._line(planes[0], "XLA Modules"))
        if line is not None:
            merged = trace._union((e.start_ns, e.end_ns) for e in line.events)
    gaps: dict = {}
    active, nxt = [], 0
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = (e0 + s1) / 2 + shift_ns
        while nxt < len(spans) and spans[nxt][0] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] >= mid]
        name = (max(active, key=lambda sp: (sp[0], -sp[1]))[2]
                if active else "other")
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) * 1e-9
    return sorted(([k, v] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:trace.TOP]


def program_spans(space) -> set:
    """The names of the program's ``fed/*`` spans in the trace."""
    return {e.name for _, events in _host_events(space) for e in events
            if e.name.startswith("fed/")}


def scope_seconds(space, program: str = PROGRAM) -> tuple[dict, list]:
    """{scope or ``unscoped``: device seconds} of ``program``'s leaf
    operations, averaged over the devices, and the unscoped operations
    that took most time ([[HLO name, seconds]])."""
    planes = trace._device_planes(space)
    out: dict = {}
    unscoped: dict = {}
    for plane in planes:
        ids = set()
        mod_line = trace._line(plane, "XLA Modules")
        for ev in (mod_line.events if mod_line is not None else ()):
            m = re.fullmatch(r"(.*)\((\d+)\)", ev.name)
            if m and m.group(1) == program:
                ids.add(int(m.group(2)))
        op_line = trace._line(plane, "XLA Ops")
        for ev in (op_line.events if op_line is not None and ids else ()):
            opcode, tf_op, pid = plane.op(ev.metadata_id)
            if pid not in ids or opcode in NON_LEAF:
                continue
            k, sec = _scope(tf_op), ev.duration_ns * 1e-9 / len(planes)
            out[k] = out.get(k, 0.0) + sec
            if k == "unscoped":
                op = trace._op_name(ev.name)
                unscoped[op] = unscoped.get(op, 0.0) + sec
    top = sorted(([k, v] for k, v in unscoped.items()), key=lambda kv: -kv[1])
    return out, top[:trace.TOP]


def reduce(space, window_s: float, span_names) -> dict:
    """``trace.reduce``'s summary, with its idle gaps named by
    :func:`idle_gaps` among ``span_names`` and the program's spans after
    the clock shift, and ``clock_shift_ns``, ``scopes`` and
    ``unscoped_ops`` added."""
    out = trace.reduce(space, window_s, span_names)
    shift = clock_shift_ns(space)
    scopes, unscoped_ops = scope_seconds(space)
    names = set(span_names) | program_spans(space)
    out.update(clock_shift_ns=shift, idle_gaps=idle_gaps(space, shift, names),
               scopes=scopes, unscoped_ops=unscoped_ops)
    return out


def reduce_dir(directory: str, window, span_names) -> dict:
    """:func:`reduce` of the newest trace under ``directory``; ``window``
    is the (start, end) of the traced window on ``time.perf_counter``."""
    path = trace.find_xplane(directory)
    if path is None:
        return {"devices": 0, "busy_s": 0.0, "window_s": window[1] - window[0],
                "modules": {}, "device_ops": [], "idle_gaps": [],
                "clock_shift_ns": 0.0, "scopes": {}, "unscoped_ops": []}
    return reduce(load(path), window[1] - window[0], span_names)


def self_times(records, *names) -> list[float]:
    """Of each record ``(name, t0, t1)`` named in ``names``: its duration
    less the part of it that records nested in it cover. The records come
    from one thread, so a record inside another's interval is nested in
    it."""
    recs = sorted(records, key=lambda r: (r[1], -r[2]))
    out = []
    for i, (name, s0, s1) in enumerate(recs):
        if name not in names:
            continue
        covered, end = 0.0, s0
        for _, t0, t1 in recs[i + 1:]:
            if t0 > s1:
                break
            if t1 <= s1 and t1 > max(t0, end):
                covered += t1 - max(t0, end)
                end = t1
        out.append(s1 - s0 - covered)
    return out


def train_layers(summary: dict, records, window, *, rounds: int,
                 evals: int) -> dict:
    """The training cells' per-layer numbers that the benchmark's readers
    cannot yet read (``programs_per_round.train`` has its reader): device
    milliseconds per round of each scope (``local_steps`` without the ghost
    pull nested in it), the host's self time per round in selection and
    dispatch and in the replay tail (evaluations left out), per evaluation
    in server eval (its wait for the logits left out), and the set-up
    spans' seconds. A number whose spans or scopes the run did not record
    is left out."""
    out = {}
    scopes = summary.get("scopes", {})
    if rounds and any(s in scopes for s in SCOPES):
        for s in SCOPES:
            out[f"{s}_ms.train"] = 1e3 * scopes.get(s, 0.0) / rounds
    inside = [r for r in records if window[0] <= r[1] and r[2] <= window[1]]
    if rounds and any(r[0] == "fed/replay" for r in inside):
        out["host_prep_ms.train"] = 1e3 * sum(self_times(
            inside, "fed/select", "fed/dispatch")) / rounds
        out["host_replay_ms.train"] = 1e3 * sum(self_times(
            inside, "fed/replay")) / rounds
    if evals and any(r[0] == "fed/eval" for r in inside):
        out["eval_host_ms.train"] = 1e3 * sum(self_times(
            inside, "fed/eval")) / evals
    for metric, name in (("partition_s.train", "fed/partition"),
                         ("engine_build_s.train", "fed/engine-build"),
                         ("first_call_s.train", "fed/run")):
        first = next((t1 - t0 for n, t0, t1 in records if n == name), None)
        if first is not None:
            out[metric] = first
    return out
