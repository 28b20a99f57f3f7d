"""The scope and program-span reduction and its readers, on a trace the
test builds (hand arithmetic) and on one it records.

The built trace has what a TPU trace has: ``XLA Ops`` events whose
metadata names the program id and the instruction, the program's HLO
(``Hlo Proto`` on ``/host:metadata``) with each instruction's op_name, and
the program's host spans."""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import harness  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000_000          # one millisecond in picoseconds
PROGRAM = 77
NEW = ("attn_share.serve", "attn_share.offline", "solver_share.serve",
       "idle_in_fetch.serve", "idle_in_fetch.offline")
OLD = ("device_idle.serve", "device_idle.offline", "taa_kernel_share")
BODY = "jit(program)/vmap()/while/body/closed_call/"

# (instruction, op_name, start_ms, dur_ms): window 0..100 ms; busy 10-60,
# 70-75 and 95-100 (the last op straddles the window's end, so it counts
# as busy but not as a whole call)
DEVICE = [
    ("fusion.1", "parataa/denoise/dit/attn/bnd,dhk->bnhk/dot_general",
     10, 20),
    ("fusion.2", "parataa/denoise/dit/mlp/dot_general", 30, 15),
    # a fusion whose root is outside every scope: it takes its fused
    # slice's scope
    ("slice_bitcast_fusion.3", "broadcast_in_dim", 45, 5),
    ("_taa_apply.2", "parataa/anderson/jit(_taa_apply_jit)/pallas_call",
     50, 5),
    ("fusion.4", "parataa/residual/dot_general", 55, 5),
    ("copy.3", "", 70, 5),
    ("fusion.5", "parataa/denoise/dit/attn/exp", 95, 10),
]
FUSED = {"slice_bitcast_fusion.3": [("slice.9",
                                     "parataa/denoise/dit/weights/slice")]}
HOST = [
    (trace_reduce.WINDOW_EVENT, 0, 100),
    ("loop.idle", 0, 9),             # idle 0-9
    ("stepwise.step", 40, 2),        # device busy: no idle under it
    ("stepwise.poll", 58, 8),        # idle 60-66
    ("np.asarray(jax.Array)", 59, 6),
    ("stepwise.harvest", 74, 6),     # idle 75-80
    ("stepwise.poll", 85, 2),        # idle 85-87
]


# -- a small protobuf encoder for the HloProto ------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A message from (field, value): ints as varints, the rest as
    length-delimited bytes."""
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += _varint(field << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(field << 3 | 2) + _varint(len(value)) + value
    return out


def _hlo_proto(scoped):
    """HloProto > hlo_module > computations (id) > instructions (name,
    opcode, metadata.op_name, called_computation_ids)."""
    def inst(name, op_name, opcode="fusion", calls=()):
        fields = [(1, name), (2, opcode)]
        if scoped and op_name:
            fields.append((7, _msg((2, BODY + op_name))))
        if calls:
            fields.append((38, b"".join(_varint(c) for c in calls)))
        return _msg(*fields)

    fused = [_msg((5, 100 + i), *((2, inst(n, o, "slice"))
                                  for n, o in FUSED[name]))
             for i, name in enumerate(FUSED)]
    calls = {name: [100 + i] for i, name in enumerate(FUSED)}
    entry = _msg((5, 1), *((2, inst(n, o, calls=calls.get(n, ())))
                           for n, o, _, _ in DEVICE))
    return _msg((1, _msg(*((3, c) for c in fused + [entry]))))


# -- the XSpace, as a text proto ---------------------------------------------


def _escape(data):
    return "".join(f"\\{b:03o}" for b in data)


def _plane(pid, name, events=(), line="", metadata=(), stats=()):
    """One plane: events (metadata id, start_ms, dur_ms) on one line;
    metadata (id, name, display name, [(stat id, field, value)]); stat
    names (id, name)."""
    evs = " ".join(f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                   f"duration_ps: {int(d * MS)} }}" for m, s, d in events)
    lines = f'lines {{ id: 1 name: "{line}" timestamp_ns: 0 {evs} }}' \
        if events else ""
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        f'display_name: "{dn}" '
        + " ".join(f"stats {{ metadata_id: {sid} {field}: {value} }}"
                   for sid, field, value in st)
        + " } }" for i, n, dn, st in metadata)
    snames = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                      f'name: "{n}" }} }}' for i, n in stats)
    return f'planes {{ id: {pid} name: "{name}" {lines} {meta} {snames} }}'


def _xspace(scoped=True):
    """The test trace, serialized; ``scoped=False`` is the same trace as a
    program without named scopes and host spans records it."""
    from jax.profiler import ProfileData
    dev_meta = [(i + 1, f"%{n} = f32[8] fusion(f32[8] %p), kind=kOutput", n,
                 [(1, "uint64_value", PROGRAM)])
                for i, (n, _, _, _) in enumerate(DEVICE)]
    dev_events = [(i + 1, s, d) for i, (_, _, s, d) in enumerate(DEVICE)]
    host = [h for h in HOST if scoped or "." not in h[0]
            or h[0].startswith("np.")]
    names = sorted({n for n, _, _ in host})
    host_meta = [(i + 1, n, n, []) for i, n in enumerate(names)]
    host_events = [(names.index(n) + 1, s, d) for n, s, d in host]
    text = (
        _plane(1, "/device:TPU:0", dev_events, trace_reduce.DEVICE_OP_LINE,
               dev_meta, [(1, "program_id")])
        + _plane(2, "/host:CPU", host_events, "python", host_meta)
        + _plane(3, "/host:metadata", metadata=[
            (1, f"jit_program({PROGRAM})", "",
             [(1, "bytes_value", f'"{_escape(_hlo_proto(scoped))}"')])],
            stats=[(1, "Hlo Proto")]))
    return ProfileData.text_proto_to_serialized_xspace(text)


def _ctx(data, taa=True):
    """What the harness hands the readers for this trace."""
    from jax.profiler import ProfileData
    red = trace_reduce.reduce(trace_reduce.from_profile(
        ProfileData.from_serialized_xspace(data)))
    return {"trace": red, "taa": {"T": 25} if taa else None}


@pytest.fixture
def placed(tmp_path, monkeypatch):
    """Lay a trace file where the harness keeps its trace: a directory of
    the temporary root."""
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))

    def place(data):
        where = tmp_path / "tmpdir" / "plugins" / "profile" / "1"
        where.mkdir(parents=True, exist_ok=True)
        (where / "host.xplane.pb").write_bytes(data)
        scopes._cache.clear()
        return data
    yield place
    scopes._cache.clear()


def test_scope_paths():
    assert scopes.scope_path(BODY + "parataa/denoise/dit/attn/"
                             "bnd,dhk->bnhk/dot_general") == \
        "parataa/denoise/dit/attn"
    assert scopes.scope_path(
        "jit(program)/parataa/anderson/jit(_taa_apply_jit)/pallas_call") \
        == "parataa/anderson"
    assert scopes.scope_path("jit(f)/dit/mlp/mul") == "dit/mlp"
    assert scopes.scope_path("jit(program)/concatenate") == ""
    assert scopes.scope_path("") == ""


def test_scope_seconds_and_idle_under(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    red = scopes.reduce(scopes.read_xspace(str(path)))
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.060) and red.devices == 1
    assert red.scope_seconds == pytest.approx({
        "parataa": 0.050, "parataa/denoise": 0.040,
        "parataa/denoise/dit": 0.040, "parataa/denoise/dit/attn": 0.020,
        "parataa/denoise/dit/mlp": 0.015,
        "parataa/denoise/dit/weights": 0.005, "parataa/anderson": 0.005,
        "parataa/residual": 0.005})
    assert red.ending("dit/attn") == pytest.approx(0.020)
    under = red.idle_under
    assert under["loop.idle"] == pytest.approx(0.009)
    assert under["stepwise.step"] == 0.0
    assert under["stepwise.poll"] == pytest.approx(0.008)
    assert under["stepwise.harvest"] == pytest.approx(0.005)
    assert under["np.asarray(jax.Array)"] == pytest.approx(0.005)


def test_new_readers_by_hand(placed):
    ctx = _ctx(placed(_xspace()))
    read = {name: harness.metric_reader(name)(ctx) for name in NEW}
    assert read["attn_share.serve"] == pytest.approx(100 * 20 / 60)
    assert read["attn_share.offline"] == read["attn_share.serve"]
    assert read["solver_share.serve"] == pytest.approx(100 * 10 / 60)
    assert read["idle_in_fetch.serve"] == pytest.approx(13.0)
    assert read["idle_in_fetch.offline"] == read["idle_in_fetch.serve"]
    # never more idle under the fetches than idle in all
    assert read["idle_in_fetch.serve"] <= \
        harness.metric_reader("device_idle.serve")(ctx)


def test_existing_readers_are_unmoved_by_scopes_and_spans():
    with_new, without = _ctx(_xspace(True)), _ctx(_xspace(False))
    for name in OLD:
        value = harness.metric_reader(name)(with_new)
        assert value is not None
        assert value == pytest.approx(harness.metric_reader(name)(without))
    assert harness.metric_reader("taa_kernel_share")(with_new) == \
        pytest.approx(100 * 5 / 60)
    assert trace_reduce.top_ops(with_new["trace"]) == \
        trace_reduce.top_ops(without["trace"])


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_their_inputs(placed, name):
    read = harness.metric_reader(name)
    assert read({"trace": None, "taa": {"T": 25}}) is None
    # a program with neither scopes nor spans, as the parent records
    assert read(_ctx(placed(_xspace(scoped=False)))) is None
    if name.startswith("solver_share"):
        assert read(_ctx(placed(_xspace()), taa=False)) is None


def test_only_the_trace_of_this_window_is_read(placed):
    """A trace file whose window differs from the reduction's is some
    other run's: nothing is read from it."""
    ctx = _ctx(placed(_xspace()))
    ctx["trace"] = dataclasses.replace(ctx["trace"], window_s=0.2)
    assert harness.metric_reader("attn_share.serve")(ctx) is None


def test_reads_a_recorded_trace(tmp_path):
    """On a recorded trace the program's spans arrive under their own
    names and its HLO carries the named scopes; the CPU backend has no
    device plane, so nothing is reduced."""
    import jax
    import jax.numpy as jnp
    from repro.obs import SpanTracer

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("dit"), jax.named_scope("attn"):
            return jnp.sin(x) @ x

    x = jnp.ones((16, 16))
    scoped_program(x).block_until_ready()
    tracer = SpanTracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
        with tracer.span("stepwise.poll"):
            scoped_program(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    trace = scopes.read_xspace(str(path))
    assert "stepwise.poll" in {name for name, _, _ in trace.host}
    assert trace.window() is not None
    assert scopes.reduce(trace) is None
    # the program's own HLO, as the trace keeps it, names the scope
    with open(path, "rb") as f:
        data = memoryview(f.read())
    op_names = {}
    for name, _, meta in (scopes._plane(v) for k, v in scopes._fields(data)
                          if k == 1):
        if name == "/host:metadata":
            for mname, _, stats in meta.values():
                if mname.startswith("jit_scoped_program("):
                    op_names = scopes._op_names(stats[scopes.HLO_PROTO_STAT])
    assert "dit/attn" in {scopes.scope_path(n) for n in op_names.values()}
