"""The device as jax reports it, the refusal of anything but a TPU, the
compile cache and the compile counter."""
from __future__ import annotations

import os
import sys
import time


class NoChip(SystemExit):
    pass


def place_compile_cache(root):
    """jax's persistent cache at a fixed path inside the checkout (the
    path is part of the key).  Where JAX_COMPILATION_CACHE_DIR is set the
    operator has placed it and nothing is set in code — the program's own
    rule (framework/init.py), kept here so both agree.  Small programs
    are cached too: every run is a new process and pays each compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_tpu(chips):
    """The devices, or exit non-zero with no result: a measurement path
    that finds no chip fails, it does not fall back to the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"perfbench: no accelerator: {e}", file=sys.stderr)
        raise NoChip(3)
    if devices[0].platform != "tpu":
        print(f"perfbench: platform is {devices[0].platform!r}, not a TPU — "
              f"the benchmark runs on the chip only", file=sys.stderr)
        raise NoChip(3)
    if len(devices) < chips:
        print(f"perfbench: the cell asks for {chips} chips, jax sees "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(3)
    return devices[:chips]


def peak_bytes(devices):
    """The peak on the fullest chip so far: the allocator's peak in use
    plus its peak reserved — the TPU runtime books a running program's
    temporaries as reserved, not as in use (the train step: 4.95 GB of
    state in use, 5.92 GB of temporaries reserved).  A process's peak
    never falls, so WHEN it is read decides what it covers."""
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0))
               for s in (d.memory_stats() or {} for d in devices))


def describe(devices, window_peak_bytes):
    """The device as jax reports it.  `memory_peak_bytes` is the peak the
    runner read as its window closed — the served or trained state's,
    before the reference ran; the peak as the process ends (the check's
    memory included) stays beside it for the record."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(window_peak_bytes),
            "memory_peak_bytes_at_exit": peak_bytes(devices),
            "memory_stats": devices[0].memory_stats() or {}}


class CompileCounter:
    """Backend compiles and persistent-cache reads seen by jax itself (a
    read still stalls the caller), and ProfiledJit's CompileLedger beside.
    `since(mark)` is the larger of the two deltas."""

    def __init__(self):
        import jax.monitoring as mon

        self.events = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            self.events.append((time.perf_counter(), secs))

    def mark(self):
        from paddle_tpu.profiler import compile_ledger

        return (len(self.events), compile_ledger.total())

    def since(self, mark):
        from paddle_tpu.profiler import compile_ledger

        return max(len(self.events) - mark[0],
                   compile_ledger.total() - mark[1])

    def seconds(self):
        return sum(s for _, s in self.events)


class SetupClock:
    """Set-up split by phase, printed to stderr as it goes."""

    def __init__(self, t0):
        self.t0 = t0
        self.last = t0
        self.phases = []

    def phase(self, name):
        now = time.perf_counter()
        self.phases.append((name, now - self.last))
        print(f"[setup] {name:<28s}{now - self.last:8.2f} s", file=sys.stderr,
              flush=True)
        self.last = now

    def as_dict(self):
        return {n: round(s, 3) for n, s in self.phases}
