"""Per-jit cost attribution (reference: the per-op FLOPs/bytes the
reference's device_tracer + profiler summary attribute to kernels; here
attribution is per NAMED COMPILED PROGRAM — the unit of work on TPU).

``profiled_jit(name, fun, **jit_kwargs)`` wraps ``jax.jit``: compilation
goes through the AOT path (``lower().compile()``) once per input
signature so the compiled executable's ``cost_analysis()`` (FLOPs, bytes
accessed) and ``memory_analysis()`` are captured and attributed to
``name`` in the process-wide ``cost_registry``, together with compile
count/time and per-call wall time.  Subsequent same-signature calls hit
the cached executable directly — one dict lookup + signature hash of
overhead on the hot path.  Anything the AOT path cannot handle falls
back to the plain jitted callable (still counted, just without cost
attribution).

Compile ledger (the runtime twin of the ``retrace-hazard`` static
checker, docs/ANALYSIS.md): every new-signature compile of a profiled
program is also appended to the process-global ``compile_ledger``, and
``compile_budget(n)`` turns a code region into an assertion about how
many compiles it may trigger::

    with compile_budget(0, prefix="serving."):   # raise mode
        for _ in range(32):
            engine.step()        # steady-state decode must not retrace

    with compile_budget(None) as cb:             # record mode
        fleet_run()
    assert cb.compiles() == {"serving.decode": 1, ...}   # exact pins

Raise mode (``limit`` an int) raises :class:`CompileBudgetExceeded` at
exit when the region compiled more than ``limit`` programs; record mode
(``limit=None``) never raises — tests assert on the per-name delta,
which is how the serving suite pins "a 2-replica fleet compiles each
shared program exactly once" and "a bucket change retraces exactly
once".
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

__all__ = ["profiled_jit", "ProfiledJit", "JitCostRegistry",
           "cost_registry", "device_memory_stats",
           "CompileLedger", "compile_ledger", "compile_budget",
           "CompileBudget", "CompileBudgetExceeded"]


def _leaf_sig(x):
    # hot path: jax Arrays expose hashable .shape/.dtype/.weak_type —
    # keying on the objects themselves (no str()/tuple() conversion)
    # keeps the per-call signature cost in the tens of µs even for
    # many-layer KV pytrees
    try:
        return (x.shape, x.dtype, x.weak_type)
    except AttributeError:
        pass
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:   # numpy and friends
        return (tuple(shape), dtype, False)
    return ("py", type(x).__name__, x if isinstance(
        x, (int, float, bool, str, bytes, type(None))) else id(x))


def _signature(args, kwargs):
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(map(_leaf_sig, leaves)))


def device_memory_stats() -> Dict[str, Any]:
    """Live per-device memory stats (bytes_in_use etc).  Empty on
    backends that do not report them (CPU)."""
    out = {}
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — optional introspection
            pass
        if stats:
            out[str(d)] = dict(stats)
    return out


class _Entry:
    __slots__ = ("calls", "fallback_calls", "compile_count",
                 "compile_time_s", "call_time_s", "flops",
                 "bytes_accessed", "peak_temp_bytes", "signatures")

    def __init__(self):
        self.calls = 0
        self.fallback_calls = 0
        self.compile_count = 0
        self.compile_time_s = 0.0
        self.call_time_s = 0.0
        self.flops = 0.0           # of the most recent compile
        self.bytes_accessed = 0.0  # of the most recent compile
        self.peak_temp_bytes = 0
        self.signatures: Dict[str, dict] = {}


class JitCostRegistry:
    """name -> compile/flops/bytes/latency attribution (thread-safe)."""

    def __init__(self):
        self._entries: Dict[str, _Entry] = {}
        self._lock = threading.Lock()

    def _entry(self, name: str) -> _Entry:
        with self._lock:
            e = self._entries.get(name)
            if e is None:
                e = self._entries[name] = _Entry()
            return e

    def record_compile(self, name: str, sig_key: str, compile_s: float,
                       cost: Optional[dict], mem: Optional[Any]):
        e = self._entry(name)
        info = {"compile_time_s": compile_s}
        if cost:
            info["flops"] = float(cost.get("flops", 0.0))
            info["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        if mem is not None:
            info["temp_bytes"] = int(
                getattr(mem, "temp_size_in_bytes", 0))
            info["argument_bytes"] = int(
                getattr(mem, "argument_size_in_bytes", 0))
            info["output_bytes"] = int(
                getattr(mem, "output_size_in_bytes", 0))
        with self._lock:
            e.compile_count += 1
            e.compile_time_s += compile_s
            if cost:
                e.flops = info.get("flops", 0.0)
                e.bytes_accessed = info.get("bytes_accessed", 0.0)
            if mem is not None:
                e.peak_temp_bytes = max(e.peak_temp_bytes,
                                        info.get("temp_bytes", 0))
            e.signatures[sig_key] = info

    def record_call(self, name: str, dt: float, fallback: bool = False):
        e = self._entry(name)
        with self._lock:
            e.calls += 1
            e.call_time_s += dt
            if fallback:
                e.fallback_calls += 1

    def snapshot(self) -> Dict[str, dict]:
        """Per-name attribution incl. derived totals (total_flops =
        flops-of-current-program x calls)."""
        with self._lock:
            out = {}
            for name, e in self._entries.items():
                out[name] = {
                    "calls": e.calls,
                    "fallback_calls": e.fallback_calls,
                    "compile_count": e.compile_count,
                    "compile_time_s": e.compile_time_s,
                    "call_time_s": e.call_time_s,
                    "flops": e.flops,
                    "bytes_accessed": e.bytes_accessed,
                    "total_flops": e.flops * e.calls,
                    "peak_temp_bytes": e.peak_temp_bytes,
                    "signatures": {k: dict(v)
                                   for k, v in e.signatures.items()},
                }
            return out

    def reset(self):
        with self._lock:
            self._entries = {}


cost_registry = JitCostRegistry()


# --- compile ledger ----------------------------------------------------------
class CompileLedger:
    """Process-global per-callable trace/compile counter.

    Append-only and monotonic (``reset()`` exists for test isolation):
    every new-signature compile of a :class:`ProfiledJit` program lands
    here as ``(name, sig_key, fallback)``.  ``cost_registry`` keeps the
    rich attribution; the ledger keeps the ORDERED history cheap enough
    to diff, which is what :func:`compile_budget` pins against."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._events: List[Tuple[str, str, bool]] = []

    def on_compile(self, name: str, sig_key: str,
                   fallback: bool = False):
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + 1
            self._events.append((name, sig_key, fallback))

    def counts(self, prefix: Optional[str] = None) -> Dict[str, int]:
        """name -> compiles so far (optionally prefix-filtered)."""
        with self._lock:
            return {k: v for k, v in self._counts.items()
                    if prefix is None or k.startswith(prefix)}

    def total(self, prefix: Optional[str] = None) -> int:
        return sum(self.counts(prefix).values())

    def events(self) -> List[Tuple[str, str, bool]]:
        with self._lock:
            return list(self._events)

    def reset(self):
        with self._lock:
            self._counts = {}
            self._events = []


compile_ledger = CompileLedger()


class CompileBudgetExceeded(AssertionError):
    """A ``compile_budget`` region compiled more programs than allowed."""


class CompileBudget:
    """Context manager diffing the compile ledger across a region.

    ``limit`` is the maximum number of compiles the region may trigger
    (0 pins "no retrace at all"); ``None`` selects record mode — never
    raises, the caller asserts on :meth:`compiles` / :meth:`total`.
    ``names`` / ``prefix`` scope which programs count."""

    def __init__(self, limit: Optional[int] = None, *,
                 names: Optional[Tuple[str, ...]] = None,
                 prefix: Optional[str] = None,
                 ledger: Optional[CompileLedger] = None):
        self.limit = limit
        self.names = tuple(names) if names else None
        self.prefix = prefix
        self._ledger = ledger if ledger is not None else compile_ledger
        self._start: Dict[str, int] = {}

    def _filtered(self, counts: Dict[str, int]) -> Dict[str, int]:
        out = counts
        if self.prefix is not None:
            out = {k: v for k, v in out.items()
                   if k.startswith(self.prefix)}
        if self.names is not None:
            out = {k: v for k, v in out.items() if k in self.names}
        return out

    def compiles(self) -> Dict[str, int]:
        """Per-name compiles since entry (zero-delta names omitted)."""
        now = self._filtered(self._ledger.counts())
        return {k: v - self._start.get(k, 0) for k, v in now.items()
                if v - self._start.get(k, 0) > 0}

    def total(self) -> int:
        return sum(self.compiles().values())

    def __enter__(self) -> "CompileBudget":
        self._start = self._filtered(self._ledger.counts())
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None and self.limit is not None:
            delta = self.compiles()
            total = sum(delta.values())
            if total > self.limit:
                detail = ", ".join(f"{k} x{v}"
                                   for k, v in sorted(delta.items()))
                raise CompileBudgetExceeded(
                    f"region compiled {total} program(s), budget is "
                    f"{self.limit}: {detail} — a jitted signature "
                    "drifted (see docs/ANALYSIS.md retrace-hazard)")
        return False


def compile_budget(limit: Optional[int] = None, *,
                   names: Optional[Tuple[str, ...]] = None,
                   prefix: Optional[str] = None,
                   ledger: Optional[CompileLedger] = None
                   ) -> CompileBudget:
    """Assert a code region's compile count: ``with compile_budget(0,
    prefix="serving."): ...`` raises :class:`CompileBudgetExceeded` when
    any scoped program (re)compiles; ``compile_budget(None)`` records
    only — assert on ``cb.compiles()`` for exact per-program pins."""
    return CompileBudget(limit, names=names, prefix=prefix,
                         ledger=ledger)


class ProfiledJit:
    """A jax.jit wrapper with per-signature AOT compile + cost capture."""

    def __init__(self, name: str, fun, registry: Optional[JitCostRegistry]
                 = None, **jit_kwargs):
        self.name = name
        self._fun = fun
        self._jit = jax.jit(fun, **jit_kwargs)
        self._registry = registry if registry is not None else cost_registry
        self._compiled: Dict[Any, Any] = {}
        self._lock = threading.Lock()

    def _compile_for(self, sig, args, kwargs):
        t0 = time.perf_counter()
        lowered = self._jit.lower(*args, **kwargs)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        cost = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            cost = ca
        except Exception:  # noqa: BLE001 — backend-optional introspection
            pass
        mem = None
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001
            pass
        self._registry.record_compile(self.name, self._sig_str(sig), dt,
                                      cost, mem)
        compile_ledger.on_compile(self.name, self._sig_str(sig))
        return compiled

    @staticmethod
    def _sig_str(sig) -> str:
        _, leaves = sig
        return ",".join(
            f"{tuple(s[0])}:{s[1]}" if s[0] != "py" else repr(s[2])
            for s in leaves) or "()"  # s[1] may be a dtype object — ok

    def __call__(self, *args, **kwargs):
        try:
            sig = _signature(args, kwargs)
            compiled = self._compiled.get(sig)
        except Exception:  # unhashable leaf — plain jit handles it
            sig = compiled = None
        if sig is not None and compiled is None:
            with self._lock:
                compiled = self._compiled.get(sig)
                if compiled is None:
                    # a compile error surfaces as itself: a retry through
                    # plain jit would re-raise it later under another
                    # name, or hide a kernel the compiler refused
                    compiled = self._compile_for(sig, args, kwargs)
                    self._compiled[sig] = compiled
        # timer starts AFTER compilation: compile time is attributed
        # separately (record_compile) and must not pollute call latency
        t0 = time.perf_counter()
        if compiled is not None:
            # no fallback on failure here: the signature key pins the
            # avals, and re-running through plain jit after a failed
            # call could touch already-donated buffers (the engine
            # donates its KV pools) — masking the real error
            out = compiled(*args, **kwargs)
            self._registry.record_call(self.name,
                                       time.perf_counter() - t0)
            return out
        out = self._jit(*args, **kwargs)
        self._registry.record_call(self.name, time.perf_counter() - t0,
                                   fallback=True)
        return out

    # passthroughs so a ProfiledJit can stand in for a jax.jit callable
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def __repr__(self):
        return f"ProfiledJit({self.name!r}, {self._fun!r})"


def profiled_jit(name: str, fun=None, *, registry=None, **jit_kwargs):
    """``jax.jit`` with cost attribution under ``name``.  Usable directly
    (``profiled_jit("decode", fn, donate_argnums=(1,))``) or as a
    decorator (``@profiled_jit("decode")``)."""
    if fun is None:
        def deco(f):
            return ProfiledJit(name, f, registry=registry, **jit_kwargs)
        return deco
    return ProfiledJit(name, fun, registry=registry, **jit_kwargs)
