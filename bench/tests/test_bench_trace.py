"""The trace reduction, on a trace the test builds and on one it records."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import trace_reduce  # noqa: E402

MS = 1_000_000_000          # one millisecond in picoseconds


def _space(device_events, host_events):
    """An XSpace text proto: one device plane with an ``XLA Ops`` line and
    one host plane with one thread line; events as (name, start_ms, dur_ms).
    """
    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = " ".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * MS)} "
            f"duration_ps: {int(d * MS)} }}" for n, s, d in events)
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')
    return (plane(1, "/device:TPU:0", trace_reduce.DEVICE_OP_LINE,
                  device_events)
            + plane(2, "/host:CPU", "python", host_events))


def _reduce(device_events, host_events):
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(_space(device_events,
                                                 host_events))
    return trace_reduce.reduce(trace_reduce.from_profile(profile))


def test_busy_idle_ops_and_gaps():
    # window 0..100 ms; ops 10-30, 25-40 (overlapping), 60-70, and one op
    # that straddles the window's end
    dev = [("fusion.1", 10, 20), ("_gram_kernel", 25, 15),
           ("fusion.1", 60, 10), ("fusion.2", 95, 10)]
    host = [(trace_reduce.WINDOW_EVENT, 0, 100),
            ("PjitFunction(step)", 42, 10), ("TransferToHost", 45, 3)]
    red = _reduce(dev, host)
    assert red.window_s == pytest.approx(0.100)
    # union: 10-40, 60-70, 95-100 => 45 ms busy
    assert red.busy_s == pytest.approx(0.045)
    assert red.idle_share == pytest.approx(0.55)
    # whole calls only: the straddling fusion.2 is left out of op stats
    assert red.op_seconds == pytest.approx({"fusion.1": 0.030,
                                            "_gram_kernel": 0.015})
    assert red.op_calls == {"fusion.1": 2, "_gram_kernel": 1}
    assert red.matching("gram") == (pytest.approx(0.015), 1)
    # gaps: 40-60 (20 ms), 70-95 (25), 0-10 (10), longest first
    assert [round(s, 6) for _, s in red.gaps] == [0.025, 0.020, 0.010]
    label = red.gaps[1][0]
    assert label.startswith("+0.040s") and "PjitFunction(step)" in label
    assert trace_reduce.top_ops(red) == [
        ["fusion", pytest.approx(0.030)], ["_gram_kernel",
                                           pytest.approx(0.015)]]


def test_op_kinds_group_the_layers():
    name = ("%fusion.2763 = (f32[8,25,256]) fusion(f32[8,25,256] %a), "
            "kind=kOutput, calls=%fused_computation.12")
    assert trace_reduce.op_kind(name) == "fusion kOutput"
    assert trace_reduce.op_kind(
        '%_taa_apply_jit.2 = f32[8,25,4096] custom-call(f32[8] %x), '
        'custom_call_target="tpu_custom_call"') == "_taa_apply_jit"
    assert trace_reduce.op_kind("%slice-done.12 = f32[2] x") == "slice-done"


def test_no_window_or_no_device_reads_nothing():
    assert _reduce([("fusion.1", 0, 1)], [("other", 0, 5)]) is None
    trace = trace_reduce.Trace(device_ops={},
                               host=[(trace_reduce.WINDOW_EVENT, 0, 5)])
    assert trace_reduce.reduce(trace) is None


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load(str(tmp_path))
    lo, hi = trace.window()
    assert hi > lo
    # the CPU backend has no device plane: nothing to reduce
    assert trace_reduce.reduce(trace) is None
