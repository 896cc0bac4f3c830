"""The reduction from a profiler trace to busy time, idle share, op totals
and attributed idle gaps, on a small synthetic ``.xplane.pb``."""

import pytest

from bench import trace as tr

# Times in microseconds from the line's start (1 ms after the epoch).
# Device 0 runs fusion.1 over [0, 2) and [4, 5), copy.2 over [1, 3) and
# [8, 9); device 1 runs fusion.1 over [0, 6). The window is [0, 10); the
# host is in bench.step over [0, 5) and in bench.sync over [5, 10).
DEVICE0 = [(1, 0, 2), (2, 1, 2), (1, 4, 1), (2, 8, 1)]
DEVICE1 = [(1, 0, 6)]
HOST = [(1, 0, 10), (2, 0, 5), (3, 5, 5)]


def _line(events, name):
    evs = " ".join(
        f"events {{ metadata_id: {m} offset_ps: {a * 10**6} "
        f"duration_ps: {d * 10**6} }}" for m, a, d in events)
    return f'lines {{ id: 1 name: "{name}" timestamp_ns: 1000000 {evs} }}'


def _meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData

    ops = _meta(["fusion.1", "copy.2"])
    text = "\n".join([
        f'planes {{ id: 1 name: "/device:TPU:0" {_line(DEVICE0, "XLA Ops")} '
        f'{_line([(1, 0, 10)], "XLA Modules")} {ops} }}',
        f'planes {{ id: 2 name: "/device:TPU:1" {_line(DEVICE1, "XLA Ops")} '
        f'{ops} }}',
        f'planes {{ id: 3 name: "/host:CPU" {_line(HOST, "python")} '
        f'{_meta(["bench.window", "bench.step", "bench.sync"])} }}',
    ])
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_reduce_recorded_file(xplane):
    got = tr.reduce_file(xplane)
    assert got["window_s"] == pytest.approx(10e-6)
    # device 0 busy [0,3) + [4,5) + [8,9) = 5 us; device 1 6 us; mean 5.5
    assert got["busy_s"] == pytest.approx(5.5e-6)
    assert got["idle_share"] == pytest.approx(0.45)
    assert got["devices"] == 2
    ops = dict(got["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(9e-6)   # 2 + 1 + 6
    assert ops["copy.2"] == pytest.approx(3e-6)     # 2 + 1
    idle = got["breakdown"]["idle_gaps"]
    # device 0 idle: [3,4) in bench.step, [5,8) and [9,10) in bench.sync
    assert idle[0][0].startswith("bench.sync (2 gaps")
    assert idle[0][1] == pytest.approx(4e-6)
    assert idle[1][0].startswith("bench.step (1 gaps")
    assert idle[1][1] == pytest.approx(1e-6)


def test_op_totals_count_only_ops_that_hold_no_other():
    events = [("%while.1", 0.0, 10.0), ("%fusion.2", 1.0, 3.0),
              ("%fusion.3", 4.0, 6.0), ("%copy.4", 5.0, 12.0)]
    assert [e[0] for e in tr.leaves(events)] == [
        "%fusion.2", "%fusion.3", "%copy.4"]
    got = tr.reduce({"/device:TPU:0": events}, [])
    assert dict(got["breakdown"]["device_ops"]) == {
        "%fusion.2": 2.0, "%fusion.3": 2.0, "%copy.4": 7.0}
    assert got["busy_s"] == 12.0
    assert tr.op_name("%fusion.12 = bf16[4]{0} fusion(%x), kind=kLoop") \
        == "%fusion.12"


def test_modules_line_is_not_counted_as_ops(xplane):
    ops, _ = tr.read_events(xplane)
    assert sorted(ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(ops["/device:TPU:0"]) == 4


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(2, 3), (0, 1), (0.5, 2.5)], [(0, 3)]),
    ([(0, 1), (2, 3), (2.5, 2.6)], [(0, 1), (2, 3)]),
    ([(1, 1), (0, 0.5)], [(0, 0.5)]),
])
def test_union(intervals, merged):
    assert tr.union(intervals) == merged


def test_gaps_and_clip():
    busy = tr.union(tr.clip([(-1, 1), (3, 4), (9, 12)], 0, 10))
    assert busy == [(0, 1), (3, 4), (9, 10)]
    assert tr.gaps(busy, 0, 10) == [(1, 3), (4, 9)]
    assert tr.gaps([], 0, 2) == [(0, 2)]
    assert tr.length(busy) == 3


def test_attribute_takes_the_innermost_span_at_the_middle():
    spans = [("bench.outer", 0, 10), ("bench.inner", 2, 4),
             ("bench.other", 4, 10)]
    assert tr.attribute((2, 4), spans) == "bench.inner"
    assert tr.attribute((3, 9), spans) == "bench.other"
    assert tr.attribute((8, 11), spans) == "bench.other"
    assert tr.attribute((20, 21), spans) == "host.other"


def test_reduce_without_window_span_uses_op_extent():
    got = tr.reduce({"/device:TPU:0": [("a", 1.0, 2.0), ("b", 3.0, 5.0)]}, [])
    assert got["window_s"] == 4.0
    assert got["busy_s"] == 3.0
    assert got["breakdown"]["idle_gaps"] == [
        ["host.other (1 gaps, longest 1000.000 ms)", 1.0]]


def test_reduce_refuses_an_empty_trace():
    with pytest.raises(ValueError):
        tr.reduce({}, [])
