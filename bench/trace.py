"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

One reduction, kept with the benchmark, so every run computes device busy
time, idle share, op and scope time, collective time and idle gaps in the
same way:

* device planes are the planes whose name matches ``device_pattern``
  (``/device:TPU:<n>`` on the chip); their operations are the events of the
  ``XLA Ops`` line, or, on a backend without one, the events that carry an
  ``hlo_op`` stat (the CPU);
* the window is the host span named ``window_span`` (the benchmark opens it
  around the traced window), or the span of all operations if there is none;
* busy time is the union of operation intervals inside the window, averaged
  over the devices; idle gaps are its complement on the first device, each
  labelled with the benchmark host span (``span_prefix``) that overlaps it
  most;
* an operation's stable name is its name-scope path (the ``tf_op`` /
  ``long_name`` stat where the trace has one, else its HLO instruction
  name) with XLA's numeric suffixes removed, so a metric finds it again
  after a rebuild. Ops nest (a ``while`` op spans its body's ops), so op
  seconds may add up to more than the busy time;
* programs are the events of the ``XLA Modules`` line: their device
  seconds inside the window, and the runs that lie wholly inside it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|reducescatter|all_gather|all_reduce|"
    r"reduce_scatter|collective_permute", re.I)
_SUFFIX = re.compile(r"\.\d+")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_MODULE_ID = re.compile(r"\(\d+\)$")
_PATH_STATS = ("tf_op", "long_name", "name_scope", "op_name")


def find_xplane(path) -> str:
    """The newest ``.xplane.pb`` at or under ``path``."""
    path = os.fspath(path)
    if path.endswith(".xplane.pb"):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def stable(name: str) -> str:
    """An op or module name without XLA's numeric suffixes and ids."""
    return _SUFFIX.sub("", _MODULE_ID.sub("", name))


def _op_path(name: str, stats: dict) -> str:
    """An op's stable name: its name-scope path where the trace carries
    one, else its instruction name and result type without layouts (a TPU
    trace names an op by the whole HLO instruction,
    ``%fusion.4 = f32[4]{0} fusion(...)`` → ``fusion f32[4]``)."""
    for key in _PATH_STATS:
        v = stats.get(key)
        if isinstance(v, str) and v:
            return stable(v)
    head, _, rhs = name.partition(" = ")
    head = stable(head.lstrip("%"))
    if not rhs:
        return head
    rhs = _LAYOUT.sub("", _LAYOUT.sub("", rhs))
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        kind = rhs[:i + 1]
    else:
        kind = rhs.split(" ", 1)[0]
    return f"{head} {kind}"[:160]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _device_events(plane):
    """(ops, modules) of one device plane as lists of
    (start_ns, end_ns, name, stats)."""
    lines = {line.name: line for line in plane.lines}
    ops, modules = [], []
    if "XLA Ops" in lines:
        for ev in lines["XLA Ops"].events:
            ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                        _stats(ev)))
    else:
        for line in plane.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_op" in st:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, st))
    if "XLA Modules" in lines:
        for ev in lines["XLA Modules"].events:
            modules.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            stable(ev.name)))
    return ops, modules


def reduce(path, *, device_pattern=r"^/device:TPU:\d+$",
           window_span="bench/window", span_prefix="bench/",
           top=10) -> dict:
    """Reduce one trace; see the module docstring. Times are in seconds,
    per device (averaged over the device planes)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    dev_re = re.compile(device_pattern)
    devices, spans = [], []
    for plane in pd.planes:
        if dev_re.search(plane.name):
            devices.append((plane.name, _device_events(plane)))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if not devices:
        raise ValueError(f"no device plane matches {device_pattern!r}")
    devices.sort()
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if windows:
        lo, hi = windows[0]
    else:
        starts = [s for _, (ops, _) in devices for s, _, _, _ in ops]
        ends = [e for _, (ops, _) in devices for _, e, _, _ in ops]
        if not starts:
            raise ValueError("the trace holds no device operation")
        lo, hi = min(starts), max(ends)
    n = len(devices)
    busy_total = 0.0
    coll_total = 0.0
    paths = defaultdict(float)
    modules = {}
    gaps = []
    for d, (_, (ops, mods)) in enumerate(devices):
        inside = []
        for s, e, name, st in ops:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            inside.append((s, e))
            dur = (e - s) / 1e9
            path = _op_path(name, st)
            paths[path] += dur / n
            if COLLECTIVE.search(path) or COLLECTIVE.search(name) or \
                    COLLECTIVE.search(str(st.get("hlo_category", ""))):
                coll_total += dur
        for s, e, name in mods:
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            m = modules.setdefault(name, {"s": 0.0, "runs": 0})
            m["s"] += (ce - cs) / n / 1e9
            if d == 0 and lo <= s and e <= hi:
                m["runs"] += 1
                m.setdefault("first_start_s", (s - lo) / 1e9)
                m["last_start_s"] = (s - lo) / 1e9
        merged = _union(inside)
        busy_total += sum(e - s for s, e in merged)
        if d == 0:
            prev = lo
            for s, e in merged + [[hi, hi]]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(g, spans, window_span), (g[1] - g[0]) / 1e9]
            for g in gaps[:top]]
    ops_top = sorted(paths.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "collective_s": coll_total / n / 1e9,
        "paths": dict(paths),
        "modules": modules,
        "device_ops": [[k, v] for k, v in ops_top],
        "idle_gaps": idle,
    }


def _label(gap, spans, window_span) -> str:
    """The benchmark span that overlaps ``gap`` most (the window itself
    only where nothing else does)."""
    best, best_ov = "no host span", 0
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if name == window_span:
            ov = ov * 1e-9            # any other span wins over the window
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def seconds_under(tr: dict, fragment: str) -> float:
    """Device seconds of the ops whose stable path contains ``fragment``."""
    return sum(v for k, v in tr["paths"].items() if fragment in k)
