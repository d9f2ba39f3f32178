"""Profiler (reference: fluid/profiler.py:255 profiler context,
platform/profiler.h:127 RecordEvent, device_tracer.h CUPTI timeline).

TPU-native: jax.profiler (XPlane/TensorBoard trace — libtpu's tracer
subsumes DeviceTracer) + RecordEvent.  RecordEvent is the ONE way the
program opens a span, and every event is three things at once:

* a span on ``paddle_tpu.profiler.tracer``'s thread-local stack
  (parent/child links, Chrome-trace exportable via
  ``paddle_tpu.profiler.export_chrome_trace``; host clock);
* a ``jax.profiler.TraceAnnotation`` — while a ``jax.profiler`` trace
  runs it is a TraceMe event on the thread's line of the host plane,
  its args as the event's stats, on the clock the device planes use
  (so device idle time can be laid against it).  Nothing switches it:
  a running trace is what turns it on, and without one it costs ~1 us;
* a ``jax.named_scope``, which only names the HLO of whatever is being
  traced inside it — it emits nothing when a compiled step runs.

The summary table reads the tracer's aggregate registry, which is
lock-protected (the old module-level defaultdict dropped counts under
concurrent ``__exit__``).
"""
from __future__ import annotations

import contextlib

import jax

from ..profiler import chrome_trace as _chrome_trace
from ..profiler.tracer import tracer as _tracer

_active_trace_dir = None


def _trace_args(args):
    """What a TraceMe event can carry as stats: numbers and short
    strings.  Anything else stays on the tracer span only."""
    return {k: (int(v) if isinstance(v, bool) else v)
            for k, v in args.items()
            if isinstance(v, (int, float))
            or (isinstance(v, str) and len(v) <= 64)}


class RecordEvent:
    """RAII op-scope timer (platform/profiler.h:127): a hierarchical
    tracer span, a TraceMe event in a running ``jax.profiler`` trace,
    and a jax.named_scope (HLO names at trace time)."""

    def __init__(self, name, **args):
        self.name = name
        self._args = args or None

    def __enter__(self):
        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()
        self._mark = jax.profiler.TraceAnnotation(
            self.name, **_trace_args(self._args or {}))
        self._mark.__enter__()
        self._span = _tracer.begin(self.name, self._args)
        return self

    def set(self, **args):
        """Add args known only once the work is done (an admission's
        count, a consume's tokens) — to the span and to the event."""
        if self._span.args is None:
            self._span.args = {}
        self._span.args.update(args)
        self._mark.set_metadata(**_trace_args(args))

    def __exit__(self, *exc):
        _tracer.end(self._span)
        self._mark.__exit__(*exc)
        self._scope.__exit__(*exc)
        return False


def start_profiler(state="All", tracer_option="Default",
                   log_dir="/tmp/paddle_tpu_prof"):
    """Start the device trace (jax.profiler / XPlane) AND host-span
    retention (Chrome-trace exportable)."""
    global _active_trace_dir
    _active_trace_dir = log_dir
    _tracer.enable(clear=True)
    jax.profiler.start_trace(log_dir)


def stop_profiler(sorted_key=None, profile_path=None, timeline_path=None):
    """Stop tracing.  ``profile_path`` receives the summary TABLE (the
    reference wrote its profile proto there; the old code ignored it);
    ``timeline_path`` receives the Chrome-trace JSON of the host spans."""
    global _active_trace_dir
    if _active_trace_dir is not None:
        jax.profiler.stop_trace()
        _active_trace_dir = None
    # symmetric with start_profiler's enable(): stop retaining spans, or
    # a long-lived process would buffer up to the 1M-span cap forever
    # (retained spans stay readable/exportable until the next enable)
    _tracer.disable()
    if timeline_path:
        _chrome_trace.export_chrome_trace(timeline_path)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(summary(sorted_key or "total") + "\n")
    if sorted_key:
        print(summary(sorted_key))


def reset_profiler():
    _tracer.reset_aggregates()
    _tracer.clear()


def summary(sorted_key="total"):
    aggs = _tracer.aggregates()
    key_fns = {
        "total": lambda kv: -kv[1]["total_s"],
        "calls": lambda kv: -kv[1]["calls"],
        "max": lambda kv: -kv[1]["max_s"],
        "min": lambda kv: -kv[1]["min_s"],
        "ave": lambda kv: -kv[1]["avg_s"],
    }
    rows = sorted(aggs.items(), key=key_fns.get(sorted_key,
                                                key_fns["total"]))
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
             f"{'Min(ms)':>12}{'Max(ms)':>12}"]
    for name, a in rows:
        lines.append(
            f"{name:<40}{a['calls']:>8}{a['total_s'] * 1e3:>12.3f}"
            f"{a['avg_s'] * 1e3:>12.3f}{a['min_s'] * 1e3:>12.3f}"
            f"{a['max_s'] * 1e3:>12.3f}")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             tracer_option="Default"):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
