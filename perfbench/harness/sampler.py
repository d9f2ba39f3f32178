"""Samples the program's own counters (framework.monitor.stat_registry)
on a thread while a traced run measures.  Reads only; the program is not
touched."""
from __future__ import annotations

import threading
import time


class CounterSampler:
    def __init__(self, prefix="serving.", interval_s=0.05):
        from paddle_tpu.framework.monitor import stat_registry

        self._reg = stat_registry
        self.prefix = prefix
        self.interval_s = interval_s
        self.samples = []          # (monotonic seconds, {name: value})
        self._stop = threading.Event()
        self._thread = None

    def read(self):
        return {k: v for k, v in self._reg.stat_values().items()
                if k.startswith(self.prefix)}

    def histograms(self):
        return {k: (h.count, h.sum)
                for k, h in self._reg.histograms().items()
                if k.startswith(self.prefix)}

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            self.samples.append((time.monotonic(), self.read()))

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="perfbench-sampler")
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def between(self, start, end):
        return [(t, v) for t, v in self.samples if start <= t < end]
