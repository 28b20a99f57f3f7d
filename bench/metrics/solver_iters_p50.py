"""Median ParaTAA iterations of the requests completed in the window
(``SampleResult.iters``)."""
import statistics


def read(ctx):
    if ctx["sequential"] or not ctx["completed_iters"]:
        return None
    return float(statistics.median(ctx["completed_iters"]))
