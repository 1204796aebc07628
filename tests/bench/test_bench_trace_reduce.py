"""``bench/trace_reduce.py`` on a small trace recorded on a v5e, against
values read from the trace by hand."""
import pytest

from bench_small import load_recorded

from bench import trace_reduce

# host clock, ns: the bench.window span
WINDOW = (42_560_347, 55_580_885)
# the device's programs, device clock: (start, duration)
MODULES = [(41_496_628, 9_351), (45_150_986, 7_817), (45_735_526, 9_341),
           (49_393_954, 7_823), (50_080_184, 9_350), (53_724_380, 7_824)]
# the least shift that starts no program before the host enqueued it: the
# sixth program's enqueue at 55_030_185 against its start at 53_724_380
SHIFT = 1_305_805


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(load_recorded())


def test_window_and_busy(summary):
    assert summary.window == WINDOW
    assert summary.window_s == pytest.approx(13.020538e-3, abs=1e-12)
    (dev,) = summary.devices
    assert dev.name == "/device:TPU:0"
    assert dev.busy_s == pytest.approx(sum(d for _, d in MODULES) * 1e-9,
                                       abs=1e-15)
    assert summary.idle_share(dev) == pytest.approx(
        1 - 51_506 / 13_020_538, abs=1e-12)


def test_ops_summed_by_name(summary):
    (dev,) = summary.devices
    assert dev.op_s == pytest.approx({
        "fusion": (7_605 + 7_605 + 7_602) * 1e-9,
        "cosine_reduce_fusion": (7_815 + 7_821 + 7_820) * 1e-9,
        "copy-done": (1_727 + 1_716 + 1_727) * 1e-9,
        "copy-start": 3 * 13e-9}, abs=1e-15)
    assert [name for name, _ in summary.top_ops(2)] == [
        "cosine_reduce_fusion", "fusion"]
    share = summary.op_share(dev, lambda op: "fusion" in op)
    assert share == pytest.approx(46_268 / 13_020_538, abs=1e-12)


def test_gaps_on_the_host_clock(summary):
    (dev,) = summary.devices
    starts = [s + SHIFT for s, _ in MODULES]
    ends = [s + SHIFT + d for s, d in MODULES]
    assert dev.gaps == [(WINDOW[0], starts[0])] + list(
        zip(ends[:-1], starts[1:])) + [(ends[-1], WINDOW[1])]
    labels = dict(summary.gap_labels())
    # the three long gaps fall in the host's three sleeps
    assert labels["host.sleep"] == pytest.approx(
        (3_645_007 + 3_649_087 + 3_634_846) * 1e-9, abs=1e-15)
    assert sum(labels.values()) == pytest.approx(
        summary.window_s - dev.busy_s, abs=1e-12)


def test_helpers():
    assert trace_reduce.op_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3"
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4),
                                                                   (5, 9)]
    assert trace_reduce.label_of(5, [(4, 6, "inner"), (0, 10, "outer")]) == \
        "inner"
    assert trace_reduce.label_of(11, [(0, 10, "outer")]) == \
        trace_reduce.UNLABELLED
    ops, mods = trace_reduce.shift_to_host([(1, 2, "a")], [(1, 3, "m")],
                                           [10, 11])
    assert (ops, mods) == ([(10, 11, "a")], [(10, 12, "m")])
    # more programs than enqueues, or pairs that disagree by more than
    # PAIR_BAND: no pairing, nothing moves
    mods = [(1, 3, "m"), (4, 5, "m")]
    assert trace_reduce.shift_to_host([], mods, [10])[1] == mods
    far = 4 + 2 * trace_reduce.PAIR_BAND
    assert trace_reduce.shift_to_host([], mods, [10, far])[1] == mods


def test_device_trace_cut_short(summary):
    """A device whose events end before the host's last solve starts is
    judged over the window up to its last event; a device that ran to the
    end over the whole window."""
    (dev,) = summary.devices
    assert summary.covered(dev) == 1.0

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Event:
        def __init__(self, name, start, duration):
            self.name, self.start_ns, self.duration_ns = name, start, duration

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [
            Plane("/device:TPU:0", [Line("XLA Modules", [
                Event("m", 100, 50), Event("m", 200, 50)])]),
            Plane("/device:TPU:1", [Line("XLA Modules", [
                Event("m", 100, 50), Event("m", 900, 50)])]),
            Plane("/host:CPU", [Line("python", [
                Event("bench.window", 0, 1000), Event("bench.solve", 10, 300),
                Event("bench.solve", 400, 500)])])]
    cut = trace_reduce.summarize(Profile())
    first, second = cut.devices
    assert cut.window == (0, 1000)
    assert first.window == (0, 250) and cut.covered(first) == 0.25
    assert cut.idle_share(first) == pytest.approx(0.6)
    assert second.window == (0, 1000)
    assert cut.idle_share(second) == pytest.approx(0.9)
