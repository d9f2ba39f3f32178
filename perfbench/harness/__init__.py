"""perfbench.harness — the yardstick: traffic generation, clocks, the
reduction from traces and counters to metrics, the peaks and the shape
functions.  Nothing here knows a cell by name; cells, configurations,
traffic mixes and per-layer metrics are data files found through
BENCHMARK.json (see manifest.py)."""
