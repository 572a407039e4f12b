"""The trace reduction on a small trace built here event by event, in the
XSpace format the JAX profiler writes (planes, lines, events with
picosecond offsets from their line's start), so that every number it
should give is known."""
import pytest
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from bench import reduce

MS = 1_000_000  # nanoseconds
PS = 1000  # picoseconds per nanosecond


def _xspace_class():
    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="xplane_test.proto", package="tensorflow.profiler",
        syntax="proto3")

    def msg(name, fields, entry=False):
        m = fd.message_type.add(name=name)
        for fname, num, typ, rep, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=T.LABEL_REPEATED if rep
                            else T.LABEL_OPTIONAL)
            if tname:
                f.type_name = f".tensorflow.profiler.{tname}"
        if entry:
            m.options.map_entry = True

    msg("XEvent", [("metadata_id", 1, T.TYPE_INT64, 0, None),
                   ("offset_ps", 2, T.TYPE_INT64, 0, None),
                   ("duration_ps", 3, T.TYPE_INT64, 0, None)])
    msg("XLine", [("id", 1, T.TYPE_INT64, 0, None),
                  ("name", 2, T.TYPE_STRING, 0, None),
                  ("timestamp_ns", 3, T.TYPE_INT64, 0, None),
                  ("events", 4, T.TYPE_MESSAGE, 1, "XEvent")])
    msg("XEventMetadata", [("id", 1, T.TYPE_INT64, 0, None),
                           ("name", 2, T.TYPE_STRING, 0, None)])
    msg("XPlane_EventMetadataEntry",
        [("key", 1, T.TYPE_INT64, 0, None),
         ("value", 2, T.TYPE_MESSAGE, 0, "XEventMetadata")], entry=True)
    msg("XPlane", [("id", 1, T.TYPE_INT64, 0, None),
                   ("name", 2, T.TYPE_STRING, 0, None),
                   ("lines", 3, T.TYPE_MESSAGE, 1, "XLine"),
                   ("event_metadata", 4, T.TYPE_MESSAGE, 1,
                    "XPlane_EventMetadataEntry")])
    msg("XSpace", [("planes", 1, T.TYPE_MESSAGE, 1, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("tensorflow.profiler.XSpace"))


def _plane(space, pid, name, lines):
    """lines: {line name: [(event name, start ms, duration ms), ...]}"""
    pl = space.planes.add(id=pid, name=name)
    ids = {}
    for li, (lname, events) in enumerate(lines.items()):
        line = pl.lines.add(id=li, name=lname, timestamp_ns=0)
        for ev, start, dur in events:
            if ev not in ids:
                ids[ev] = len(ids) + 1
                pl.event_metadata[ids[ev]].id = ids[ev]
                pl.event_metadata[ids[ev]].name = ev
            line.events.add(metadata_id=ids[ev],
                            offset_ps=int(start * MS * PS),
                            duration_ps=int(dur * MS * PS))


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    space = _xspace_class()()
    # the window opens at 100 ms and lasts 100 ms (seconds=0.1)
    _plane(space, 1, "/host:CPU", {
        "python": [("bench_window", 100, 150), ("serve", 105, 10),
                   ("stall", 130, 30), ("serve", 160, 5)],
        "chunk-prefetch": [("prep", 120, 45)],
    })
    # chip 0: busy 100-125 (two overlapping ops) and 165-190; chip 1:
    # 110-130.  Modules: one scoring program, one allocator program.
    _plane(space, 2, "/device:TPU:0", {
        "XLA Modules": [("jit_din_block(7)", 100, 25), ("jit_fn(3)", 165, 25)],
        "XLA Ops": [("fusion.1", 100, 20), ("fusion.2", 110, 15),
                    ("all-gather.4", 165, 5), ("fusion.11", 170, 20)],
    })
    _plane(space, 3, "/device:TPU:1", {
        "XLA Modules": [("jit_fn(3)", 110, 20)],
        "XLA Ops": [("fusion.1", 110, 20)],
    })
    _plane(space, 4, "/device:TPU:0 extra", {"XLA Ops": [("x", 0, 500)]})
    return ProfileData.from_serialized_xspace(space.SerializeToString())


def test_busy_idle_and_programs(profile):
    r = reduce.reduce_profile(profile, 0.1, 2)
    assert r.window_s == pytest.approx(0.1)
    # chip 0: 25 + 25 ms busy, chip 1: 20 ms -> mean 35 ms
    assert r.busy_s == pytest.approx(0.035)
    assert r.program_s("jit_din_block") == pytest.approx(0.025 / 2)
    assert r.program_s("jit_fn") == pytest.approx((0.025 + 0.020) / 2)
    assert r.collective_s == pytest.approx(0.005 / 2)
    assert r.op_s["fusion"] == pytest.approx(0.055)
    assert r.host_s["prep"] == pytest.approx(0.045)


def test_idle_gaps_are_named_by_the_host(profile):
    r = reduce.reduce_profile(profile, 0.1, 1)
    # chip 0 is idle 125-165 (40 ms, midpoint 145: the serving thread
    # stalls while the prefetch thread preps) and 190-200 (10 ms, no span)
    assert r.gap_s == pytest.approx({"stall/prep": 0.040, "host": 0.010})
    assert r.busy_s == pytest.approx(0.050)
    b = r.breakdown
    assert b["idle_gaps"][0] == ["stall/prep", pytest.approx(0.040)]
    assert [k for k, _ in b["device_ops"]] == ["fusion", "all-gather"]


def test_the_window_is_clipped(profile):
    r = reduce.reduce_profile(profile, 0.05, 1)  # window 100-150 ms
    assert r.busy_s == pytest.approx(0.025)
    assert r.gap_s == pytest.approx({"stall/prep": 0.025})


def test_no_window_mark_is_an_error(profile):
    from jax.profiler import ProfileData

    space = _xspace_class()()
    _plane(space, 2, "/device:TPU:0", {"XLA Ops": [("f", 0, 1)]})
    with pytest.raises(ValueError):
        reduce.reduce_profile(
            ProfileData.from_serialized_xspace(space.SerializeToString()),
            0.1, 1)
