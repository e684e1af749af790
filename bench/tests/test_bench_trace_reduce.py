"""The reduction from a profiler trace to busy time, op groups, exposed
collectives and idle gaps by host span: on hand-made events, and on a
small trace recorded on a TPU v5e (``bench/testdata/``)."""

from __future__ import annotations

import pytest
from _tiny import REPO

from bench import trace_reduce as tr

NS = 1e-9


def _hand():
    ops = {
        0: [("a", 0, 10), ("b", 5, 15), ("a", 20, 30), ("all-reduce.1", 30, 40),
            ("c", 35, 38), ("late", 60, 70)],
        1: [("a", 0, 50)],
    }
    spans = [(tr.WINDOW_SPAN, 0, 50), ("bench.dispatch", 14, 21),
             ("fleet.fetch", 40, 50), ("other.thing", 0, 50)]
    return tr.reduce_events(ops, spans)


def test_window_and_busy_union():
    r = _hand()
    assert r.window == (0, 50) and r.window_s == pytest.approx(50 * NS)
    assert r.busy_intervals(0) == [(0, 15), (20, 40)]
    assert r.busy_s == pytest.approx((35 + 50) / 2 * NS)
    assert r.idle_share == pytest.approx(1 - 85 / 100)
    assert "late" not in {n for n, _, _ in r.ops[0]}


def test_ops_grouped_by_name_and_pattern():
    r = _hand()
    top = dict((n, v) for n, v in r.top_ops(10))
    assert top["a"] == pytest.approx((20 + 50) / 2 * NS)
    assert top["b"] == pytest.approx(10 / 2 * NS)
    assert r.op_seconds(r"^a$") == pytest.approx(70 / 2 * NS)
    assert r.op_count("all-reduce") == 0  # 1 op over 2 devices, floored
    assert r.exposed_seconds("all-reduce") == pytest.approx((10 - 3) / 2 * NS)


def test_gaps_attributed_to_the_host_span_covering_most_of_them():
    r = _hand()
    assert r.gaps(0) == [(15, 20), (40, 50)]
    gaps = dict(r.top_gaps(10))
    assert gaps == {"fleet.fetch": pytest.approx(10 / 2 * NS),
                    "bench.dispatch": pytest.approx(5 / 2 * NS)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({0: []}, [("bench.dispatch", 0, 1)])


# A traced window of seven train-mlp4 steps, recorded on one TPU v5e with
# ``--trace 1 --seconds 0.03`` (two steps in flight), compressed with gzip.
RECORDED = REPO / "bench" / "testdata" / "train_mlp4.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_events(*tr.read_xplane(str(RECORDED)))


def test_recorded_trace_window_and_busy_union(recorded):
    r = recorded
    assert list(r.ops) == [0]
    assert r.window_s == pytest.approx(0.041465349, abs=1e-12)
    busy = r.busy_intervals(0)
    assert all(s <= e for s, e in busy)
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))
    assert r.busy_s == pytest.approx(0.038897376, abs=1e-12)
    idle = sum(e - s for s, e in r.gaps(0)) * NS
    assert idle == pytest.approx(r.window_s - r.busy_s, abs=1e-12)


def test_recorded_trace_ops_grouped_by_hlo_name(recorded):
    r = recorded
    names = {n for n, _, _ in r.ops[0]}
    assert not any(n.startswith("%") or " " in n for n in names)
    # MLP4 runs three hidden blocks through nitro_matmul: a forward and a
    # grad_W kernel each, in every one of the seven steps
    assert r.op_count(r"^nitro_matmul_fwd\.") == 21
    assert r.op_count(r"^nitro_matmul_grad_w\.") == 21
    assert r.op_seconds(r"^nitro_matmul") == pytest.approx(0.011734593,
                                                           abs=1e-12)
    top = r.top_ops(3)
    assert [n for n, _ in top] == ["fusion.1", "fusion.18", "fusion.15"]


def test_recorded_trace_gaps_owned_by_the_harness_spans(recorded):
    gaps = dict(recorded.top_gaps(10))
    assert set(gaps) == {"bench.wait", "bench.dispatch"}
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, abs=1e-12)
    assert gaps["bench.wait"] == pytest.approx(0.002398063, abs=1e-12)
