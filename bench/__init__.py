"""The on-chip benchmark of the emulated-FP64 dispatch path (``BENCHMARK.json``)."""
