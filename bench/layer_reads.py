"""The arithmetic of the per-layer metrics that more than one cell reads
under names of their own (``metrics/<name>.<cell kind>.py``)."""


def lane_useful_frac(ctx):
    """Share of the lane-iterations the stepwise banks computed in the
    window that advanced a request: the change in ``useful_iters`` over
    the change in ``device_iters`` x slots, from ``loop.bank_reports()``
    as the window closed (the banks are new when it opens).  A vacant or
    finished lane is computed all the same, so the rest is waste."""
    reports = ctx["bank_reports"]
    if not reports:
        return None
    lane_iters = sum(r["device_iters"] * r["slots"]
                     for r in reports.values())
    if not lane_iters:
        return None
    return sum(r["useful_iters"] for r in reports.values()) / lane_iters


def step_mfu(ctx):
    """The whole step's share of the chip's peak, in %: denoiser rows
    evaluated in the window (chunks x chunk_iters x slots x rows per
    lane-iteration, the engine's device NFE) times FLOPs per row, over
    window x chips x peak."""
    rows = (ctx["chunks"] * ctx["chunk_iters"] * ctx["slots"]
            * ctx["rows_per_lane_iter"])
    if not rows or ctx["peaks"] is None:        # no chip, no peak
        return None
    flops = rows * ctx["flops_per_row"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_per_s"])


def device_idle(ctx):
    """Share of the traced window, in %, in which no operation ran on the
    device: 1 - (union of device-op intervals) / window, averaged over
    chips."""
    red = ctx["trace"]
    if red is None or red.busy_s <= 0:
        return None
    return 100.0 * red.idle_share
