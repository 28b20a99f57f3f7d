"""Median queue wait: admission time minus arrival time, from the
program's ``loop.queue_wait_s`` histogram over the window's admissions."""


def read(ctx):
    return ctx["queue_wait_p50_s"]
