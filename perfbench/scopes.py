"""Device time of the train step by the program's named scopes, on top
of the reduction in ``perfbench/trace.py``.

The program names its layers with ``jax.named_scope`` (the ``sct.*``
names of ``repro.core.scopes``). A device op event in a trace is named
by its HLO instruction only (``%fusion.696 = ...``), without the
``op_name`` metadata that carries the scope, so the scope is found
through the step program's optimized HLO text: the text given, or the
``HloProto`` the profiler stores for every loaded program in the
trace's ``/host:metadata`` plane, printed by XLA. :func:`summarize`
gives :func:`trace.summarize`'s summary unchanged, plus, for the
stretch inside the ``perfbench.window`` span:

* ``scope_s``: device seconds by scope (``unscoped`` for the rest) of
  the leaf ops that ran inside runs of the step program (its
  ``XLA Modules`` events), averaged over the devices used; an
  instruction's scope is read from its HLO by :func:`scope_table`;
* ``recompute_s``: the part of those seconds spent on ops a remat
  recomputes (``rematted_computation`` in the ``op_name``);
* ``program_spans``: the durations, in seconds, of the host spans
  named ``sct.*`` that lie inside the stretch, by name.

``python perfbench/scopes.py <file.xplane.pb>`` prints the scoped
summary of a trace of the train step.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

from perfbench import trace

STEP_PROGRAM = "jit_train_step"   # the jitted step perfbench/jobs/train.py drives
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_HLO_PROTO_STAT = "Hlo Proto"


def _names():
    """The program's scope names, or None for a program that has none."""
    try:
        from repro.core import scopes
    except ImportError:
        return None
    return scopes


# ---------------------------------------------------------------- HLO --

def parse_hlo(text: str):
    """{instruction: (op_name, called fusion computation or None,
    operands)} and {computation: [its instructions]} of an HLO module's
    text."""
    instrs, comps, comp = {}, {}, None
    for line in text.splitlines():
        if not line or line[0] not in " \t":
            if line.endswith("{"):
                head = line[len("ENTRY "):] if line.startswith("ENTRY ") else line
                comp = head.split(None, 1)[0].lstrip("%")
                comps[comp] = []
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        refs = _REF.findall(line[m.end():])
        instrs[name] = (op.group(1) if op else "", calls.group(1) if calls else None, refs)
        comps[comp].append(name)
    # references to computations (calls=, to_apply=, body=, ...) are not operands
    instrs = {k: (op, calls, tuple(r for r in refs if r in instrs and r != k))
              for k, (op, calls, refs) in instrs.items()}
    return instrs, comps


def scope_table(text: str) -> dict:
    """{instruction: (scope or None, recomputed)} of an HLO module's text.

    The scope is the innermost ``sct.*`` in the instruction's ``op_name``.
    A fusion whose ``op_name`` names none takes the scope most of its
    fused instructions carry. An instruction the compiler made with no
    ``op_name`` at all (async copies, broadcasts, converts) takes the
    scope of the nearest instruction it feeds, else of the nearest that
    feeds it, passing only through other such instructions."""
    names = _names()
    instrs, comps = parse_hlo(text)
    if names is None:
        return {name: (None, False) for name in instrs}

    def fused_op_names(comp, seen):
        for name in comps.get(comp, ()):
            op, calls, _ = instrs[name]
            yield op
            if calls is not None and calls not in seen:
                seen.add(calls)
                yield from fused_op_names(calls, seen)

    table = {}
    for name, (op, calls, _) in instrs.items():
        scope, recomputed = names.scope_of(op), names.is_recomputed(op)
        if scope is None and calls is not None:
            by_scope = {}
            for inner in fused_op_names(calls, {calls}):
                s = names.scope_of(inner)
                if s is not None:
                    by_scope.setdefault(s, []).append(names.is_recomputed(inner))
            if by_scope:
                scope, marks = max(by_scope.items(), key=lambda kv: len(kv[1]))
                recomputed = 2 * sum(marks) > len(marks)
        table[name] = (scope, recomputed)

    users = {}
    for name, (_, _, operands) in instrs.items():
        for o in operands:
            users.setdefault(o, []).append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for n in frontier:
                for m in edges(n):
                    if m not in seen:
                        seen.add(m)
                        if table[m][0] is not None:
                            return table[m]
                        if not instrs[m][0]:
                            nxt.append(m)
            frontier = nxt
        return None

    for name, (op, _, operands) in instrs.items():
        if not op and table[name][0] is None:
            table[name] = (nearest(name, lambda n: users.get(n, ()))
                           or nearest(name, lambda n: instrs[n][2]) or (None, False))
    return table


def instruction(event_name: str) -> str:
    """``fusion.696`` for an op event named ``%fusion.696 = ...``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


# ----------------------------------------------------- trace metadata --

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a serialized protobuf message: ints for
    varints, memoryviews for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _first(buf, number):
    return next((v for f, v in _fields(buf) if f == number), None)


def hlo_modules(path: str, program: str) -> dict:
    """{module name: HLO text} of every run of ``program`` whose HLO the
    trace at ``path`` stores (XSpace -> the ``/host:metadata`` XPlane ->
    its event metadata, one per program, with an ``Hlo Proto`` stat)."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:                                    # XSpace.planes
            continue
        name, events, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == 2:                                     # XPlane.name
                name = bytes(v).decode()
                if name != "/host:metadata":
                    break
            elif f == 4:                                   # XPlane.event_metadata
                events.append(_first(v, 2))
            elif f == 5:                                   # XPlane.stat_metadata
                meta = _first(v, 2)
                stat_names[_first(meta, 1)] = bytes(_first(meta, 2) or b"").decode()
        if name != "/host:metadata":
            continue
        for ev in events:
            module = bytes(_first(ev, 2) or b"").decode()  # XEventMetadata.name
            if trace.program_name(module) != program:
                continue
            for f, stat in _fields(ev):
                if f != 5 or stat_names.get(_first(stat, 1)) != _HLO_PROTO_STAT:
                    continue
                proto = _first(_first(stat, 6), 1)         # bytes_value -> hlo_module
                hlo = xla_client._xla.HloModule.from_serialized_hlo_module_proto(bytes(proto))
                out[module] = hlo.to_string()
    return out


# ------------------------------------------------------------ reduce --

def read_planes(path: str):
    """:func:`trace.read_planes`, but the events of lines that share a
    name are kept together. A line is a host thread, named by the OS
    thread's name, and Python threads share the process's: the
    checkpoint writer's line must not replace the main thread's."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events)
        planes.append((plane.name, lines))
    return planes


def _window(planes):
    lo = hi = None
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for events in lines.values():
                for name, s, e in events:
                    if name == trace.WINDOW:
                        lo = s if lo is None else min(lo, s)
                        hi = e if hi is None else max(hi, e)
    return lo, hi


def summarize(planes, program: str = STEP_PROGRAM, hlo=None, n_devices: int = 1) -> dict:
    """:func:`trace.summarize` plus ``scope_s``, ``recompute_s`` and
    ``program_spans``. ``hlo`` is the program's HLO text, or a dict of
    texts by module name (``jit_train_step(123...)``) as
    :func:`hlo_modules` gives them."""
    out = trace.summarize(planes, n_devices)
    lo, hi = _window(planes)
    texts = hlo if isinstance(hlo, dict) else {}
    shared = scope_table(hlo) if isinstance(hlo, str) else None
    tables = {}

    def table_of(module):
        if shared is not None:
            return shared
        if module not in tables:
            tables[module] = scope_table(texts[module]) if module in texts else {}
        return tables[module]

    devices = sorted((p, l) for p, l in planes
                     if p.startswith("/device:") and trace.OPS_LINE in l)[:n_devices]
    scope_s, recompute_s = {}, 0.0
    for _, lines in devices:
        runs = []
        for name, s, e in lines.get(trace.MODULES_LINE, ()):
            s, e = max(s, lo), min(e, hi)
            if e > s and trace.program_name(name) == program:
                runs.append((s, e, name))
        runs.sort()
        starts = [r[0] for r in runs]
        ops = []
        for name, s, e in lines[trace.OPS_LINE]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                ops.append((name, s, e))
        for name, s, e in trace.leaves(ops):
            mid = (s + e) / 2
            k = bisect.bisect_right(starts, mid) - 1
            if k < 0 or mid > runs[k][1]:
                continue                      # not inside a run of the program
            scope, recomputed = table_of(runs[k][2]).get(instruction(name), (None, False))
            key = scope or UNSCOPED
            scope_s[key] = scope_s.get(key, 0.0) + (e - s) / 1e9
            if recomputed:
                recompute_s += (e - s) / 1e9
    n = max(len(devices), 1)
    spans = {}
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for events in lines.values():
                for name, s, e in events:
                    if name.startswith("sct.") and lo <= s and e <= hi:
                        spans.setdefault(name, []).append((e - s) / 1e9)
    out.update(scope_s={k: v / n for k, v in scope_s.items()}, recompute_s=recompute_s / n,
               program_spans=spans)
    return out


def reduce(path: str, program: str = STEP_PROGRAM, hlo=None, n_devices: int = 1) -> dict:
    """The scoped summary of the trace at ``path``; the HLO comes from
    the trace itself unless ``hlo`` is given."""
    if hlo is None:
        hlo = hlo_modules(path, program)
    return summarize(read_planes(path), program, hlo, n_devices)


# ----------------------------------------------------------- readers --

def of_run(ctx: dict, root: str):
    """The scoped summary of the traced stretch a metric reader's
    ``ctx`` describes, or None where the program names no ``sct.*``
    scope. The trace is the newest under ``<root>/perfbench_out/traces``
    whose stretch matches ``ctx["trace"]``; the result is kept in
    ``ctx`` for the next reader."""
    if "scoped_trace" in ctx:
        return ctx["scoped_trace"]
    ctx["scoped_trace"] = None
    tr = ctx.get("trace")
    if not tr or _names() is None:
        return None
    found = glob.glob(os.path.join(root, "perfbench_out", "traces", "**", "*.xplane.pb"),
                      recursive=True)
    n = ctx.get("chips", 1)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        planes = read_planes(path)
        try:
            if trace.summarize(planes, n)["window_s"] != tr["window_s"]:
                continue
        except ValueError:                    # a trace of no stretch, or of no device
            continue
        s = summarize(planes, STEP_PROGRAM, hlo_modules(path, STEP_PROGRAM), n)
        if any(k != UNSCOPED for k in s["scope_s"]):
            ctx["scoped_trace"] = s
        break
    return ctx["scoped_trace"]


def scope_ms(ctx: dict, root: str, *scope_names: str):
    """Device milliseconds per traced step in the given scopes, or None."""
    s = of_run(ctx, root)
    if s is None:
        return None
    return 1000.0 * sum(s["scope_s"].get(k, 0.0) for k in scope_names) / ctx["steps_traced"]


if __name__ == "__main__":
    summary = reduce(sys.argv[1])
    keep = ("window_s", "busy_s", "idle_share", "modules", "scope_s", "recompute_s")
    print(json.dumps({k: summary[k] for k in keep}, indent=1))
    print(json.dumps({k: [len(v), sum(v) / len(v)] for k, v in
                      summary["program_spans"].items()}, indent=1))
