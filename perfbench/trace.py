"""Trace capture and its reduction to numbers.

A traced run records one stretch of its measured window with
``jax.profiler``, python tracing off, and marks the stretch on the host
with a ``perfbench.window`` annotation; the harness's own host spans
(``perfbench.<what>``) sit inside it. :func:`reduce` reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives, for the
stretch only:

* ``window_s``: the stretch's length;
* ``busy_s``: the union of the intervals in which an operation ran on
  the device, averaged over the devices used, and ``idle_share``;
* ``ops`` and ``modules``: device seconds by operation (leaf events of
  the ``XLA Ops`` line, by :func:`short_name`) and by program
  (``XLA Modules``, by :func:`program_name`);
* ``module_events`` and ``custom_calls``: the first device's program
  runs (full names, hash included) and custom-call ops, as
  (name, start, end), for readers that tell programs apart by the
  kernels they run;
* ``gaps``: idle gaps on the first device, longest first, each named
  by the host span that was open at its middle.

``python perfbench/trace.py <file.xplane.pb>`` prints the planes, lines
and a few events of a trace, for reading one by hand.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import sys

WINDOW = "perfbench.window"
SPAN_PREFIX = "perfbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_KIND = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-_]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into ``log_dir`` (emptied first)."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
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
    return total


def idle_gaps(intervals, lo: float, hi: float):
    """(start, end) of every stretch of [lo, hi] no interval covers."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def short_name(op: str) -> str:
    """``%fusion.12 fusion`` for an XLA op event named by its whole HLO
    instruction; custom calls keep their target."""
    lhs, _, rhs = op.partition(" = ")
    m = _KIND.search(rhs)
    kind = m.group(1) if m else ""
    if kind == "custom-call":
        t = _TARGET.search(rhs)
        kind += f" {t.group(1)}" if t else ""
    return f"{lhs} {kind}".strip()


def program_name(module: str) -> str:
    """``jit_train_step`` for ``jit_train_step(1049...)``."""
    return module.split("(", 1)[0]


def leaves(events):
    """Events that hold no other event of the same line: the device's
    leaf operations, whose durations add up without double counting
    (a while loop's event spans every op of its body)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    inner = [True] * len(evs)
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            inner[stack[-1]] = False
        stack.append(i)
    return [ev for ev, leaf in zip(evs, inner) if leaf]


def summarize(planes, n_devices: int = 1) -> dict:
    """The reduction over already-read planes: ``planes`` is a list of
    (plane name, {line name: [(event name, start_ns, end_ns)]})."""
    host_spans = []
    window = None
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for events in lines.values():
            for name, s, e in events:
                if name == WINDOW:
                    window = (s, e) if window is None else (min(window[0], s), max(window[1], e))
                elif name.startswith(SPAN_PREFIX):
                    host_spans.append((name[len(SPAN_PREFIX):], s, e))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = window
    devices = [(p, l) for p, l in planes if p.startswith("/device:") and OPS_LINE in l]
    devices.sort(key=lambda pl: pl[0])
    devices = devices[:n_devices]
    if not devices:
        raise ValueError(f"trace holds no device plane with an {OPS_LINE!r} line")
    busy, ops, modules, first_gaps = [], {}, {}, None
    module_events, custom_calls = [], []
    for d, (_, lines) in enumerate(devices):
        iv = []
        for name, s, e in lines[OPS_LINE]:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                iv.append((name, s, e))
        for name, s, e in leaves(iv):
            key = short_name(name)
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
            if d == 0 and "custom-call" in key:
                custom_calls.append((name, s, e))
        for name, s, e in lines.get(MODULES_LINE, ()):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                prog = program_name(name)
                modules[prog] = modules.get(prog, 0.0) + (e - s) / 1e9
                if d == 0:
                    module_events.append((name, s, e))
        spans = [(s, e) for _, s, e in iv]
        busy.append(union_length(spans) / 1e9)
        if first_gaps is None:
            first_gaps = idle_gaps(spans, lo, hi)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    gaps = []
    for s, e in sorted(first_gaps, key=lambda g: g[0] - g[1]):
        mid = (s + e) / 2
        open_spans = [(n, ss, ee) for n, ss, ee in host_spans if ss <= mid <= ee]
        # the innermost span open at the gap's middle names it
        name = min(open_spans, key=lambda x: x[2] - x[1])[0] if open_spans else "host_other"
        gaps.append((name, (e - s) / 1e9))
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "ops": ops, "modules": modules, "gaps": gaps,
            "module_events": module_events, "custom_calls": custom_calls}


def read_planes(path: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in line.events]
        planes.append((plane.name, lines))
    return planes


def reduce(path: str, n_devices: int = 1) -> dict:
    return summarize(read_planes(path), n_devices)


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary["gaps"][:top]]}


def dump(path: str, per_line: int = 5) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r} stats={[(k, str(v)[:80]) for k, v in plane.stats][:8]}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                stats = [(k, str(v)[:120]) for k, v in ev.stats]
                print(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} stats={stats[:8]}")


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 5)
