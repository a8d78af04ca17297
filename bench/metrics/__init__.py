"""One reader per per-layer metric, found by the metric's name.

Each module has ``read(ctx) -> float | None``; ``ctx`` is the traced
window as ``bench/run.py`` builds it (the reduced trace, the window's
ends in ns, the devices, the number of steps, the configuration and
traffic files, the chip's peaks, the HLO scope and the kind of each
instruction (``trace_reduce.hlo_ops``) and the host's dispatch times).  A reader that finds nothing to read
returns None and the metric is left out of the result.
"""
