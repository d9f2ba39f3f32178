"""Reduction of a profiler trace (.xplane.pb) to numbers.

jax.profiler.ProfileData reads the file with nothing but jax.  A device
plane is `/device:TPU:<n>`; its line "XLA Ops" holds one event per
executed HLO operation and "XLA Modules" one per executed program.  Host
planes hold one line per thread with the TraceMe events jax and the
runtime emit (`PjitFunction(step_fn)`, `np.asarray(jax.Array)`, ...) and
this benchmark's own TraceAnnotations (`perfbench/...`), all on one clock.

busy      union of the op intervals on a device, clipped to the window
idle      window minus busy; every gap is named by the most specific host
          event (shortest one that covers at least half of it)
per op    summed durations by label: the op's name without its numeric
          suffix, with the shapes the trace records for it where it does
"""
from __future__ import annotations

import bisect
import gzip
import os
import re
import shutil
import tempfile
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "perfbench/window"
OWN_PREFIX = "perfbench/"
NO_HOST_MARK = "no_host_mark"


class Trace:
    """devices: {index: {"ops": [(label, start_s, dur_s)], "modules":
    [...]}}; host: [(name, thread, start_s, dur_s)]."""

    def __init__(self, devices, host):
        self.devices = devices
        self.host = host
        self._window = False

    def window(self):
        if self._window is False:
            self._window = self._find_window()
        return self._window

    def _find_window(self):
        """The interval of the benchmark's own `perfbench/window`
        annotation, ended at the last device op recorded: the device
        tracer's buffer holds a second or two of a training step's ops,
        and once it is full the host stalls and nothing more is recorded
        — that tail is the profiler's, not the program's.  Without the
        annotation, the span of all device ops."""
        ops = [(s, s + d) for dev in self.devices.values()
               for _, s, d in dev["ops"] + dev["modules"]]
        marks = [(s, s + d) for n, _, s, d in self.host if n == WINDOW_MARK]
        if marks:
            lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
            last = max((b for a, b in ops if a < hi), default=hi)
            return lo, max(lo, min(hi, last))
        if not ops:
            return None
        return min(a for a, _ in ops), max(b for _, b in ops)


_SUFFIX = re.compile(r"(?:[.\-_]\d+)+$")
_SHAPE = re.compile(r"\b(?:pred|token|[sufb]f?\d+)\[[\d,]*\]")
_OPCODE = re.compile(r"[\s)}]([a-z][a-z0-9\-]*)\(")


def op_label(name):
    """The label ops are summed under.  On the TPU an event of the XLA-ops
    line is named by its HLO text, `%copy.4 = f32[3073,16,12,64]{...}
    copy(...)`: the label is the instruction's name without its numeric
    suffix, its opcode where the name does not say it, and its first two
    result shapes — `copy f32[3073,16,12,64]`.  A Mosaic kernel is a
    `custom-call` named by the scope jax traced it in (`jvp___` forward,
    `transpose_jvp___` backward, the step function's name in serving)."""
    lhs, sep, rhs = name.partition(" = ")
    base = _SUFFIX.sub("", lhs.strip().lstrip("%"))
    if not sep:
        return base
    m = _OPCODE.search(" " + rhs)
    opcode = m.group(1) if m else ""
    result = (" " + rhs)[:m.start() + 1] if m else rhs
    shapes = _SHAPE.findall(result)
    parts = [base]
    if opcode and opcode not in base:
        parts.append(opcode)
    return " ".join(parts + shapes[:2])


def load(path):
    """Read an .xplane.pb (or .xplane.pb.gz) into a Trace."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb",
                                         dir=os.path.dirname(path)) as tmp:
            with gzip.open(path, "rb") as src:
                shutil.copyfileobj(src, tmp)
            tmp.flush()
            data = ProfileData.from_file(tmp.name)
            return _from_profile(data)
    return _from_profile(ProfileData.from_file(path))


def _from_profile(data):
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [
                        (op_label(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = line.name.split("/")[0]
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, thread, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    return Trace(devices, host)


# -- interval arithmetic ------------------------------------------------------
def merge(intervals, lo, hi):
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(merged, lo, hi):
    """The complement of a merged union inside [lo, hi]."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def busy_seconds(trace, window=None):
    """(mean busy seconds over the devices, window seconds)."""
    lo, hi = window or trace.window()
    per_dev = [sum(b - a for a, b in merge(
        [(s, s + d) for _, s, d in dev["ops"]], lo, hi))
        for dev in trace.devices.values()]
    return (sum(per_dev) / len(per_dev) if per_dev else 0.0), hi - lo


def idle_share(trace, window=None):
    busy, span = busy_seconds(trace, window)
    return 1.0 - busy / span if span > 0 else None


def op_seconds(trace, window=None):
    """label -> device seconds inside the window, averaged over devices."""
    lo, hi = window or trace.window()
    total = defaultdict(float)
    for dev in trace.devices.values():
        for label, s, d in dev["ops"]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                total[label] += part
    n = max(1, len(trace.devices))
    return {k: v / n for k, v in total.items()}


def op_calls(trace, pattern, window=None):
    """(seconds, calls) of ops whose label matches, inside the window,
    averaged over devices; a call counts where it starts in the window."""
    lo, hi = window or trace.window()
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for dev in trace.devices.values():
        for label, s, d in dev["ops"]:
            if lo <= s and s + d <= hi and rx.search(label):
                secs += d
                calls += 1
    n = max(1, len(trace.devices))
    return secs / n, calls / n


def module_durations(trace, pattern, window=None):
    """Durations (s) of the executed programs whose name matches."""
    lo, hi = window or trace.window()
    rx = re.compile(pattern)
    return [d for dev in trace.devices.values()
            for name, s, d in dev["modules"]
            if lo <= s and s + d <= hi and rx.search(name)]


def gap_attribution(trace, window=None, device=None):
    """label -> idle seconds: each idle gap of one device (the first by
    default) goes to the shortest host event covering at least half of
    it, named `event (thread)`; to NO_HOST_MARK where there is none."""
    lo, hi = window or trace.window()
    if not trace.devices:
        return {}
    dev = trace.devices[sorted(trace.devices)[0] if device is None
                        else device]
    idle = gaps(merge([(s, s + d) for _, s, d in dev["ops"]], lo, hi),
                lo, hi)
    host = sorted((e for e in trace.host
                   if not e[0].startswith(OWN_PREFIX)
                   and e[2] < hi and e[2] + e[3] > lo),
                  key=lambda e: e[2])
    starts = [e[2] for e in host]
    longest = max((e[3] for e in host), default=0.0)
    out = defaultdict(float)
    for a, b in idle:
        best = None
        # candidates start before the gap's end and no earlier than the
        # longest event could reach back
        i0 = bisect.bisect_left(starts, a - longest)
        i1 = bisect.bisect_right(starts, b)
        for name, thread, s, d in host[i0:i1]:
            cover = min(s + d, b) - max(s, a)
            if cover >= 0.5 * (b - a) and (best is None or d < best[0]):
                best = (d, f"{name} ({thread})")
        out[best[1] if best else NO_HOST_MARK] += b - a
    return dict(out)


def breakdown(trace, window=None, top=10):
    ops = sorted(op_seconds(trace, window).items(), key=lambda kv: -kv[1])
    idle = sorted(gap_attribution(trace, window).items(),
                  key=lambda kv: -kv[1])
    return {"device_ops": [[k[:120], v] for k, v in ops[:top]],
            "idle_gaps": [[k[:120], v] for k, v in idle[:top]]}


def capture(log_dir):
    """Start jax's profiler with the Python tracer off (the host's
    TraceMe events stay): tracing every Python call slows the host it
    measures.  The traced slice is marked with the WINDOW_MARK annotation
    from here until the returned stop(), which ends the trace and returns
    the path of the .xplane.pb written."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
    mark.__enter__()

    def stop():
        mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        return found[-1] if found else None

    return stop
