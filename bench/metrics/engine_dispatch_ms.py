"""Median host time inside ``DeftRuntime.step`` until it returns (the
enqueue of the step), from the benchmark's own timers."""
import statistics


def read(ctx):
    d = ctx["dispatch_s"]
    return 1e3 * statistics.median(d) if d else None
