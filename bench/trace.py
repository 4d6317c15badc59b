"""From a profiler trace to busy, idle and kernel times.

Two stages.  :func:`load` reads the ``.xplane.pb`` that ``jax.profiler``
writes into plain lists: per device the operations of its ``XLA Ops``
line and the programs of its ``XLA Modules`` line, and the host's spans
(``jax.profiler.TraceAnnotation`` names among them), all in nanoseconds
on the trace's one clock.  The functions below reduce those lists; they
need no JAX, so a recorded trace tests them.
"""
from __future__ import annotations

import re
from pathlib import Path

CONTAINERS = ("while", "conditional", "call")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_META_STATS = ("long_name", "tf_op", "hlo_op", "hlo_module", "name",
               "kernel_details")


def load(path) -> dict:
    """The trace at ``path`` (a ``.xplane.pb`` file or a directory that
    holds one) as ``{"devices": {id: {"ops": [...], "modules": [...]}},
    "host": [...]}``; an op or span is ``[name, start_ns, dur_ns, meta]``
    where ``meta`` joins the event's naming stats."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)),
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is not None:
                    dev[key].extend(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"].extend(_events(line))
    return out


def _events(line) -> list:
    evs = []
    for ev in line.events:
        stats = dict(ev.stats)
        meta = " ".join(str(stats[k]) for k in _META_STATS if k in stats)
        evs.append([ev.name, float(ev.start_ns), float(ev.duration_ns), meta])
    return evs


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted (start, end) of possibly overlapping intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` that no interval of ``b``
    covers (both merged and sorted)."""
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


def spans(events) -> list:
    return [(s, s + d) for _, s, d, _ in events]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window(trace: dict, span: str) -> tuple:
    """(start, end) of the host span named ``span`` (the first one)."""
    for name, s, d, _ in trace["host"]:
        if name == span:
            return s, s + d
    raise KeyError(f"no host span {span!r} in the trace")


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation runs."""
    return length(clip(union(spans(ops)), lo, hi))


def idle_gaps(ops, lo: float, hi: float) -> list:
    """(start, end) of the stretches of [lo, hi] with no operation."""
    busy = clip(union(spans(ops)), lo, hi)
    return subtract([(lo, hi)], busy)


def label(gap, host, names) -> str:
    """The innermost host span among ``names`` that holds the middle of
    ``gap`` (the one that started last; of those, the shortest), or
    "host" when none does."""
    mid = 0.5 * (gap[0] + gap[1])
    best, key = "host", None
    for name, s, d, _ in host:
        if name in names and s <= mid <= s + d and (key is None
                                                     or (s, -d) > key):
            best, key = name, (s, -d)
    return best


def longest_gaps(trace: dict, device: int, lo: float, hi: float, names,
                 k: int = 10) -> list:
    """The ``k`` longest idle gaps of ``device`` as [label, seconds]."""
    gaps = idle_gaps(trace["devices"][device]["ops"], lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(g, trace["host"], names), (g[1] - g[0]) * 1e-9]
            for g in gaps[:k]]


def short_name(name: str) -> str:
    """``fusion.12`` of an op named by its HLO text ``%fusion.12 = ...``."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """The HLO opcode of an op named by its text ``%x = SHAPE opcode(...)``
    (a bare name such as ``while.7`` gives its stem)."""
    if " = " not in name:
        return name.lstrip("%").split(".", 1)[0]
    rest = name.split(" = ", 1)[1]
    if rest.startswith("("):                 # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0]


def is_container(name: str) -> bool:
    """A while, conditional or call op: its body's ops are events too."""
    return opcode(name) in CONTAINERS


def top_ops(trace: dict, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` operations that took most device time in [lo, hi], as
    [name, seconds] averaged over the devices; loops and calls are left
    out, since the ops of their bodies are counted."""
    tot: dict = {}
    devs = trace["devices"]
    for dev in devs.values():
        for name, s, d, _ in dev["ops"]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0 and not is_container(name):
                key = short_name(name)
                tot[key] = tot.get(key, 0.0) + part
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / len(devs)] for name, ns in ranked]


def op_time_ns(ops, lo: float, hi: float) -> float:
    """Summed device time of ``ops`` inside [lo, hi]."""
    return sum(max(0.0, min(s + d, hi) - max(s, lo)) for _, s, d, _ in ops)
