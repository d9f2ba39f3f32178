"""The program's own spans in a profiler trace, with their stats.

A `RecordEvent` of the program (utils/profiler.py) is a TraceMe event on
its thread's line of the host plane while a jax.profiler trace runs, on
the clock the device planes use; the args it was given are the event's
stats (`serving/ragged_step` carries rows, ctx_tokens, attn_pairs ...).
harness/trace.py keeps names and times only, so the file is read again
here for the events under the program's prefixes.  A program that opens
no such events (an older commit) gives an empty list, and every reader of
this module then returns nothing.
"""
from __future__ import annotations

import functools
from collections import namedtuple

from . import trace as T

PREFIXES = ("serving/", "hapi/")

Span = namedtuple("Span", "name thread start end stats")


@functools.lru_cache(maxsize=2)
def load(path):
    """Every host event of the .xplane.pb at `path` whose name starts
    with one of PREFIXES, as Span(name, thread, start_s, end_s,
    {stat: value}), by start time."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread = line.name.split("/")[0]
            for e in line.events:
                if e.duration_ns > 0 and e.name.startswith(PREFIXES):
                    start = e.start_ns * 1e-9
                    out.append(Span(e.name, thread, start,
                                    start + e.duration_ns * 1e-9,
                                    dict(e.stats)))
    return tuple(sorted(out, key=lambda s: s.start))


def of(ctx):
    """The traced run's spans, or () where there is no trace."""
    path = (ctx.get("obs") or {}).get("trace_path")
    return load(path) if path and ctx.get("trace") is not None else ()


def named(spans, names):
    names = set(names)
    return [s for s in spans if s.name in names]


def union(spans, lo, hi):
    """The merged intervals the spans cover inside [lo, hi], whatever
    thread each ran on."""
    return T.merge([(s.start, s.end) for s in spans], lo, hi)


def overlap(a, b):
    """The intervals two merged, sorted interval lists have in common."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def seconds(intervals):
    return sum(b - a for a, b in intervals)
