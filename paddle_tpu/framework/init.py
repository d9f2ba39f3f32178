"""Process-level initialization (reference: platform/init.cc —
InitDevices enumerates devices once, InitGLOG wires logging, and signal
handlers install crash stack dumps; SignalHandle in init.cc prints the
demangled C++ trace the PADDLE_ENFORCE machinery relies on).

TPU-native shape: device enumeration is jax's; what remains is (a) an
idempotent init that triggers backend discovery exactly once and records
what was found, (b) fault handlers — ``faulthandler`` dumps all-thread
Python stacks on SIGSEGV/SIGABRT/FPE the way the reference dumps C++
frames, plus an optional SIGTERM hook that flushes PS/geo state before
the launcher's watchdog kill (launch_utils.py:544 terminates pods)."""
from __future__ import annotations

import atexit
import faulthandler
import os
import signal
import sys
import threading
from typing import Callable, List, Optional

_state = {
    "initialized": False,
    "devices": [],
    "platform": None,
}
_lock = threading.Lock()
_sigterm_hooks: List[Callable[[], None]] = []


def configure_compile_cache() -> Optional[str]:
    """Place jax's persistent compilation cache; called once from
    ``import paddle_tpu``.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    operator has placed it and nothing is set in code.  Otherwise it
    goes to ``<checkout>/.jax_cache``, derived from this package's own
    path: the directory is part of the cache key, so it must not move
    between processes (no tempfile, pid or time).  A process held to the
    CPU (``JAX_PLATFORMS=cpu`` — the test suite) gets none: its compiles
    take seconds, and XLA:CPU logs a machine-feature warning on every
    cache read.  Returns the directory set here, or None.  Touches only
    ``jax.config`` — no backend is initialised."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cache_dir = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def init_devices(force: bool = False) -> list:
    """Enumerate accelerator devices once (init.cc:InitDevices analog).
    Returns the device list; safe to call from anywhere."""
    with _lock:
        if _state["initialized"] and not force:
            return _state["devices"]
        import jax

        devices = jax.devices()
        _state["devices"] = devices
        _state["platform"] = devices[0].platform if devices else None
        _state["initialized"] = True
        return devices


_handlers_installed = [False]


def init_signal_handlers(dump_path: Optional[str] = None) -> None:
    """Install crash handlers (init.cc SignalHandle analog): on
    SIGSEGV/SIGFPE/SIGABRT/SIGBUS, dump every thread's Python stack —
    the debugging affordance the reference gets from its C++ trace.
    Idempotent: repeated calls never chain handlers (hooks must run
    exactly once on SIGTERM) nor leak dump streams."""
    if _handlers_installed[0]:
        return
    _handlers_installed[0] = True
    stream = sys.stderr
    if dump_path:
        stream = open(dump_path, "a")  # noqa: SIM115 — lives past scope
        atexit.register(stream.close)
    if not faulthandler.is_enabled():
        faulthandler.enable(file=stream, all_threads=True)
    # SIGTERM: launcher watchdogs TERM the pod on a peer failure
    # (launch_utils.py:544); flush registered state first, then die with
    # the default semantics
    if threading.current_thread() is threading.main_thread():
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            for hook in list(_sigterm_hooks):
                try:
                    hook()
                except Exception:  # noqa: BLE001 — dying anyway
                    pass
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_term)


def register_shutdown_hook(fn: Callable[[], None]) -> None:
    """Run `fn` on SIGTERM before the process dies (PS table flush,
    checkpoint-on-eviction — the reference's checkpoint_notify path)."""
    _sigterm_hooks.append(fn)


def init(dump_path: Optional[str] = None) -> None:
    """Full process init (reference framework.init() / InitDevices +
    InitSignalHandler): devices + crash handlers."""
    init_devices()
    init_signal_handlers(dump_path)


def is_initialized() -> bool:
    return _state["initialized"]


def get_platform() -> Optional[str]:
    return _state["platform"]
