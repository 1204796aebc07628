"""``bench/layer_metrics/_scopes.py``: ``tf_op`` read from a recorded v5e
trace, the leaf-op rule and the idle-by-span arithmetic on hand-built
events."""
import numpy as np
import pytest

from bench_small import RECORDED_TRACE, load_recorded

from bench import harness, trace_reduce
from bench.layer_metrics import _scopes

DEVICE = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    return _scopes.read_ops(_scopes.read_space(str(RECORDED_TRACE)),
                            [DEVICE])


def test_tf_op_read_from_the_xspace(recorded):
    by_dev, _ = recorded
    ops = by_dev[DEVICE]
    tf_op = {ops.name[mid]: op for mid, op in ops.tf_op.items() if op}
    assert tf_op == {"fusion": "jit(<lambda>)/dot_general:",
                     "cosine_reduce_fusion": "jit(<lambda>)/reduce_sum:"}
    assert len(ops.start) == 12 and len(ops.modules) == 6


def test_ops_moved_as_trace_reduce_moves_them(recorded):
    """The shift onto the host's clock is the reduction's, and the leaf
    ops of the recorded trace (none nests) sum to its per-op times."""
    by_dev, enqueues = recorded
    ops = by_dev[DEVICE]
    summary = trace_reduce.summarize(load_recorded())
    (dev,) = summary.devices
    shift = _scopes.host_shift_ps(ops, enqueues)
    assert shift == 1_305_805 * 1000
    scopes = _scopes.device_scopes(summary, ops, shift, dev)
    assert scopes.covered_s == pytest.approx(dev.window_s, abs=1e-15)
    assert sum(scopes.by_tf_op.values()) == pytest.approx(
        sum(dev.op_s.values()), abs=1e-8)
    assert scopes.by_tf_op["jit(<lambda>)/dot_general:"] == pytest.approx(
        dev.op_s["fusion"], abs=1e-8)
    # the program that recorded it names no phase
    assert not scopes.named() and scopes.outside_s() == pytest.approx(
        sum(scopes.by_tf_op.values()))


def _ops(events):
    """Ops of [(start_ns, end_ns, tf_op)], in ps on the host's clock."""
    names = sorted({tf for _, _, tf in events})
    ids = {tf: i for i, tf in enumerate(names)}
    return _scopes.Ops(
        start=np.array([s * 1000 for s, _, _ in events], np.int64),
        end=np.array([e * 1000 for _, e, _ in events], np.int64),
        meta=np.array([ids[tf] for _, _, tf in events], np.int64),
        tf_op={i: tf for tf, i in ids.items()},
        name={i: tf.rsplit("/", 1)[-1] for tf, i in ids.items()},
        modules=[])


BODY = "jit(run_block)/while/body/"
NESTED = [
    (0, 100, "jit(run_block)/while"),                           # container
    (10, 40, BODY + "cola.local_solve/dot_general:"),
    (50, 90, BODY + "cola.record/cond"),                        # container
    (55, 85, BODY + "cola.record/cond/branch_1_fun/reduce_sum:"),
    (95, 100, BODY + "add:"),                       # ends with its parent
    (120, 130, BODY + "cola.local_solve/dot_general:"),
]


def test_leaf_ops_count_once():
    ops = _ops(NESTED)
    assert _scopes.leaf_mask(ops.start, ops.end).tolist() == [
        False, True, False, True, True, True]
    dev = trace_reduce.Device(name=DEVICE, busy_s=0.0, op_s={}, gaps=[],
                              window=(0, 200))
    summary = trace_reduce.Summary(window=(0, 200), devices=[dev],
                                   host_spans=[])
    scopes = _scopes.device_scopes(summary, ops, 0, dev)
    assert scopes.covered_s == pytest.approx(200e-9)
    # each container's time is its children's, counted once
    assert sum(scopes.by_tf_op.values()) == pytest.approx(75e-9)
    assert scopes.seconds(_scopes.SOLVE) == pytest.approx(40e-9)
    assert scopes.seconds(_scopes.RECORD) == pytest.approx(30e-9)
    assert scopes.seconds(_scopes.UPDATE) == 0.0
    assert scopes.outside_s() == pytest.approx(5e-9)
    assert scopes.named()
    lines = _scopes.coverage_lines(scopes, 75e-9)
    assert "outside every program scope 0.000000 s (6.6667% of busy" \
        in lines[0]
    assert any(line.strip().startswith(_scopes.SOLVE) for line in lines)


def test_op_line_cut_short():
    """A solve that starts after the op line's last event: the share is
    taken over the part of the window up to that event."""
    ops = _ops(NESTED)
    dev = trace_reduce.Device(name=DEVICE, busy_s=0.0, op_s={}, gaps=[],
                              window=(0, 1000))
    spans = [(0, 1000, "bench.window"), (0, 140, "bench.solve"),
             (150, 900, "bench.solve")]
    summary = trace_reduce.Summary(window=(0, 1000), devices=[dev],
                                   host_spans=spans)
    scopes = _scopes.device_scopes(summary, ops, 0, dev)
    assert scopes.covered_s == pytest.approx(130e-9)
    # moved 20 ns later by the host shift, the line ends at 150: no solve
    # starts after it
    scopes = _scopes.device_scopes(summary, ops, 20_000, dev)
    assert scopes.covered_s == pytest.approx(1000e-9)


# a device idle over [0, 100), [300, 400) and [600, 1000) of its window
GAPS = [(0, 100), (300, 400), (600, 1000)]
PROGRAM_SPANS = [(0, 1000, "bench.window"), (0, 1000, "bench.solve"),
                 (50, 350, "env-build"), (380, 390, "stop-sync"),
                 (390, 700, "block-dispatch")]
# what the Python tracer lays over them: shorter frames inside the spans
FRAMES = [(60, 90, "$cola.py:455 build_env"),
          (310, 340, "$metrics.py:80 make_recorder"),
          (381, 389, "$array.py:631 _value"),
          (610, 690, "$executor.py:660 run_round_blocks")]


@pytest.mark.parametrize("frames", [[], FRAMES], ids=["spans", "frames"])
def test_idle_by_span(frames):
    dev = trace_reduce.Device(name=DEVICE, busy_s=400e-9, op_s={},
                              gaps=GAPS, window=(0, 1000))
    summary = trace_reduce.Summary(window=(0, 1000), devices=[dev],
                                   host_spans=PROGRAM_SPANS + frames)
    setup = _scopes.idle_in_spans(summary, _scopes.SETUP_SPANS)
    block = _scopes.idle_in_spans(summary, _scopes.BLOCK_SPANS)
    # [50, 100) and [300, 350) under env-build; [380, 400) and [600, 700)
    # under stop-sync + block-dispatch
    assert setup == pytest.approx(10.0)
    assert block == pytest.approx(12.0)
    assert setup + block <= 100.0 * summary.idle_share(dev)
    assert _scopes.idle_in_spans(summary, ("history-fetch",)) is None


def test_overlap():
    assert _scopes.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert _scopes.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert _scopes.overlap_ns([], [(0, 1)]) == 0


def test_a_program_without_scopes_reads_none(monkeypatch):
    """Over a trace whose program names no phase, the readers return
    None and do not raise."""
    monkeypatch.setattr(_scopes, "trace_file", lambda: str(RECORDED_TRACE))
    _scopes._CACHE.clear()
    run = harness.Run(cell={}, config={}, traffic={}, chips=1,
                      device_kind="TPU v5 lite",
                      trace=trace_reduce.summarize(load_recorded()))
    for name in ("solve_share.cert", "update_share.rounds",
                 "record_share.cert", "setup_idle.rounds",
                 "block_idle.cert"):
        assert harness.reader("layer_metrics", name)(run) is None, name
    run.trace = None
    assert harness.reader("layer_metrics", "solve_share.rounds")(run) is None
