"""Observability-layer tests: telemetry, metrics registry, tracing.

The load-bearing guarantee is the telemetry guard: running
``les.train_step`` with ``telemetry=True`` must produce a
**bitwise-identical** training trajectory to telemetry-off (it is a pure
readout added as an extra jit output) and the telemetry-enabled jaxpr
must stay float-free — asserted here on the paper CNN configs.  The
registry/tracer halves are plain host-side concurrency + serialisation
tests: consistent snapshots under concurrent writers, Prometheus/JSONL
round-trips, span nesting on the monotonic clock.
"""

from __future__ import annotations

import functools
import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gradcheck import assert_bitwise_equal, assert_jaxpr_integer_only
from repro.configs import paper
from repro.core import les
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.core.numerics import ACT_MAX, ACT_MIN
from repro.obs import telemetry as T
from repro.obs.metrics import (
    MetricError,
    MetricRegistry,
    latency_summary_ms,
    percentile,
    start_metrics_server,
)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.stats import (
    EngineStats,
    fleet_snapshot_delta,
    snapshot_delta,
)

INT32_MIN = np.iinfo(np.int32).min
INT32_MAX = np.iinfo(np.int32).max


def tiny_cfg():
    return NitroConfig(
        blocks=(BlockSpec("conv", 8, pool=True, d_lr=64),
                BlockSpec("linear", 16)),
        input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
        name="tiny-obs",
    )


def _batch(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (n, *cfg.input_shape)), jnp.int32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, n), jnp.int32)
    return x, y


# ---------------------------------------------------------------------------
# telemetry: integer reductions
# ---------------------------------------------------------------------------


class TestBitWidth:
    @pytest.mark.parametrize("value,bits", [
        (0, 0), (1, 1), (-1, 1), (2, 2), (3, 2), (127, 7), (-127, 7),
        (128, 8), (255, 8), (256, 9), (2**30 - 1, 30), (2**30, 31),
        (INT32_MAX, 31), (INT32_MIN, 32), (INT32_MIN + 1, 31),
    ])
    def test_matches_bit_length(self, value, bits):
        got = int(T.bit_width(jnp.asarray([value], jnp.int32))[0])
        assert got == bits
        if value != INT32_MIN:  # python int has no two's-complement edge
            assert got == abs(value).bit_length()

    def test_random_matches_python_bit_length(self):
        rng = np.random.default_rng(0)
        vals = rng.integers(INT32_MIN, INT32_MAX, 4096, dtype=np.int64,
                            endpoint=True).astype(np.int32)
        got = np.asarray(T.bit_width(jnp.asarray(vals)))
        want = np.array([32 if v == INT32_MIN else int(abs(int(v)).bit_length())
                         for v in vals], np.int32)
        np.testing.assert_array_equal(got, want)

    def test_occupancy_is_a_histogram(self):
        rng = np.random.default_rng(1)
        vals = rng.integers(-10**6, 10**6, (64, 33), dtype=np.int64).astype(np.int32)
        hist = np.asarray(T.bit_occupancy(jnp.asarray(vals)))
        assert hist.shape == (T.NUM_BIT_BUCKETS,)
        assert hist.sum() == vals.size
        bits = np.array([int(abs(int(v)).bit_length()) for v in vals.ravel()])
        np.testing.assert_array_equal(
            hist, np.bincount(bits, minlength=T.NUM_BIT_BUCKETS))

    def test_tensor_telemetry_saturation_and_max(self):
        vals = jnp.asarray([0, 1, -127, 127, 128, -129, 2**30, INT32_MIN],
                           jnp.int32)
        tt = T.tensor_telemetry(vals)
        assert int(tt.bit_hist.sum()) == 8
        # |x| > 127: 128, -129, 2**30, INT32_MIN
        assert int(tt.sat_int8) == 4
        # |x| >= 2**30: 2**30, INT32_MIN
        assert int(tt.sat_int32) == 2
        assert int(tt.max_abs) == INT32_MAX  # INT32_MIN maps to the max mag
        for leaf in tt:
            assert "int" in str(leaf.dtype)

    def test_relu_dead_count(self):
        z = jnp.asarray([ACT_MIN - 1, ACT_MIN, 0, ACT_MAX, ACT_MAX + 1],
                        jnp.int32)
        assert int(T.relu_dead_count(z)) == 2


class TestTelemetryGuard:
    """Telemetry on vs off: bitwise-identical trajectory, float-free."""

    def _run_guard(self, cfg, batch, steps):
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        x, labels = _batch(cfg, batch)
        plain = jax.jit(functools.partial(les.train_step, cfg=cfg))
        instrumented = jax.jit(
            functools.partial(les.train_step, cfg=cfg, telemetry=True))
        s_a = s_b = state
        for i in range(steps):
            key = jax.random.PRNGKey(100 + i)
            s_a, m_a = plain(s_a, x=x, labels=labels, key=key)
            s_b, m_b, telem = instrumented(s_b, x=x, labels=labels, key=key)
        assert_bitwise_equal(s_b, s_a, err_msg=f"telemetry broke {cfg.name}")
        assert_bitwise_equal(m_b, m_a)
        for leaf in jax.tree_util.tree_leaves(telem):
            assert "int" in str(np.asarray(leaf).dtype), "float telemetry leaf"
        return state, x, labels, telem

    def test_tiny_multi_step_bitwise_identical(self):
        self._run_guard(tiny_cfg(), batch=8, steps=3)

    def test_tiny_jaxpr_integer_only(self):
        cfg = tiny_cfg()
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        x, labels = _batch(cfg, 4)
        jaxpr = jax.make_jaxpr(
            functools.partial(les.train_step, cfg=cfg, telemetry=True)
        )(state, x=x, labels=labels, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)

    def test_vgg8b_paper_config(self):
        cfg = paper.get("vgg8b", scale=0.0625)
        state, x, labels, _ = self._run_guard(cfg, batch=4, steps=2)
        jaxpr = jax.make_jaxpr(
            functools.partial(les.train_step, cfg=cfg, telemetry=True)
        )(state, x=x, labels=labels, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)

    @pytest.mark.slow
    def test_vgg11b_paper_config(self):
        cfg = paper.get("vgg11b", scale=0.0625)
        state, x, labels, _ = self._run_guard(cfg, batch=4, steps=2)
        jaxpr = jax.make_jaxpr(
            functools.partial(les.train_step, cfg=cfg, telemetry=True)
        )(state, x=x, labels=labels, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)


class TestRecords:
    def _telem(self, cfg, batch=4):
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        x, labels = _batch(cfg, batch)
        step = jax.jit(functools.partial(les.train_step, cfg=cfg,
                                         telemetry=True))
        _, _, telem = step(state, x=x, labels=labels,
                           key=jax.random.PRNGKey(1))
        return telem

    def test_to_records_shape(self):
        cfg = tiny_cfg()
        records = T.to_records(self._telem(cfg), cfg=cfg, step=7)
        layers = [r["layer"] for r in records]
        assert layers == ["block0", "block1", "output", "_opt"]
        for rec in records[:2]:
            assert rec["step"] == 7
            z = rec["z_star"]
            assert sum(z["bit_hist"]) == z["total"]
            assert 0.0 <= rec["dead_frac"] <= 1.0
            assert rec["dead"] == pytest.approx(
                rec["dead_frac"] * z["total"])
            assert z["msb"] <= 32 and z["max_abs"] >= 0
            assert 0.0 <= z["sat_int8_frac"] <= 1.0
            assert rec["alpha_inv"] == cfg.blocks[0].alpha_inv
        assert "grad" in records[2] and "weight" in records[2]
        opt = records[3]
        for k in ("gamma_inv_lr", "eta_inv_lr", "gamma_inv_fw", "eta_inv_fw"):
            assert isinstance(opt[k], int)

    def test_append_jsonl_appends(self, tmp_path):
        cfg = tiny_cfg()
        records = T.to_records(self._telem(cfg), cfg=cfg, step=0)
        path = str(tmp_path / "metrics.jsonl")
        T.append_jsonl(path, records)
        T.append_jsonl(path, records)  # append, not truncate
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert len(lines) == 2 * len(records)
        assert lines[0]["layer"] == "block0"

    def test_append_jsonl_creates_parent_dir(self, tmp_path):
        # the default telemetry path sits in a ckpt dir that may not
        # exist yet at the first sampled step
        path = str(tmp_path / "ckpts" / "metrics.jsonl")
        T.append_jsonl(path, [{"step": 0}])
        with open(path) as f:
            assert json.loads(f.read()) == {"step": 0}


class TestScaledLoss:
    def test_scaled_loss_units(self):
        from repro.core.losses import ONE_HOT_VALUE
        m = les.StepMetrics(loss=jnp.asarray(2 * ONE_HOT_VALUE ** 2),
                            correct=jnp.asarray(0),
                            local_losses=jnp.zeros(1, jnp.int32))
        assert m.scaled_loss(2) == pytest.approx(1.0)
        assert m.scaled_loss(4) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# percentile helpers: boundary behaviour (the historical off-by-one)
# ---------------------------------------------------------------------------


class TestPercentileEdges:
    def test_empty_and_single(self):
        assert percentile([], 0.5) == 0.0
        assert percentile([], 1.0) == 0.0
        for q in (0.0, 0.5, 0.99, 1.0):
            assert percentile([42.0], q) == 42.0

    def test_exact_rank_boundaries(self):
        vals = [1.0, 2.0, 3.0, 4.0]
        # q*n integral was the buggy case: floor-rank returned rank+1
        assert percentile(vals, 0.25) == 1.0
        assert percentile(vals, 0.5) == 2.0
        assert percentile(vals, 0.75) == 3.0
        assert percentile(vals, 1.0) == 4.0
        assert percentile(vals, 0.51) == 3.0

    def test_nearest_rank_invariant(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 5, 10, 100):
            vals = sorted(rng.uniform(0, 1, n).tolist())
            for q in (0.0, 0.01, 0.25, 0.5, 0.9, 0.99, 1.0):
                p = percentile(vals, q)
                assert p in vals
                # nearest-rank definition: the ceil(q·n)-th smallest
                import math
                rank = min(max(math.ceil(q * n), 1), n)
                assert p == vals[rank - 1]

    def test_latency_summary_edge_cases(self):
        assert latency_summary_ms([]) == {
            "p50": 0.0, "p90": 0.0, "p95": 0.0, "p99": 0.0}
        out = latency_summary_ms([0.005])
        assert all(v == pytest.approx(5.0) for v in out.values())
        out = latency_summary_ms([0.002, 0.001])  # unsorted input
        assert out["p50"] == pytest.approx(1.0)
        assert out["p99"] == pytest.approx(2.0)

    def test_snapshot_delta_identity_and_zero(self):
        stats = EngineStats()
        pre = stats.snapshot()
        assert snapshot_delta(pre, pre) == {
            "requests": 0, "batches": 0, "padded_slots": 0,
            "avg_batch_fill": 0.0}
        stats.record_batch(3, 1, 0.01)
        post = stats.snapshot()
        d = snapshot_delta(pre, post)
        assert d["requests"] == 3 and d["batches"] == 1
        assert d["avg_batch_fill"] == pytest.approx(0.75)

    def test_fleet_snapshot_delta_new_model(self):
        empty = {"requests": 0, "batches": 0, "padded_slots": 0,
                 "avg_batch_fill": 0.0}
        pre = {"fleet": empty, "models": {}}
        post = {"fleet": {**empty, "requests": 2, "batches": 1},
                "models": {"late": {**empty, "requests": 2, "batches": 1}}}
        d = fleet_snapshot_delta(pre, post)
        assert d["models"]["late"]["requests"] == 2  # deltaed against zero


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetricRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricRegistry()
        c = reg.counter("x_total", "a counter")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(MetricError):
            c.inc(-1)
        g = reg.gauge("depth")
        g.set(7)
        g.inc(2)
        g.dec()
        assert g.value == 8
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        child = h.labels()
        assert child.count == 3
        assert child.sum == pytest.approx(2.55)
        assert child.cumulative_buckets() == [(0.1, 1), (1.0, 2),
                                              (float("inf"), 3)]
        assert child.percentiles()["p50"] == 0.5
        assert "x_total" in reg and "nope" not in reg

    def test_labels_and_conflicts(self):
        reg = MetricRegistry()
        fam = reg.counter("req_total", "by model", labels=("model",))
        fam.labels(model="a").inc(2)
        fam.labels(model="b").inc()
        assert fam.labels(model="a").value == 2
        with pytest.raises(MetricError):
            fam.labels(wrong="a")
        with pytest.raises(MetricError):
            fam.inc()  # label-less proxy on a labelled family
        # identical re-registration is idempotent, conflicts raise
        assert reg.counter("req_total", labels=("model",)) is fam
        with pytest.raises(MetricError):
            reg.gauge("req_total")
        with pytest.raises(MetricError):
            reg.counter("req_total", labels=("other",))
        with pytest.raises(MetricError):
            reg.counter("bad name!")
        with pytest.raises(MetricError):
            reg.histogram("empty_buckets", buckets=())
        reg.histogram("h", buckets=(1.0,), window=8)
        with pytest.raises(MetricError):
            reg.histogram("h", buckets=(2.0,), window=8)

    def test_histogram_window_is_bounded(self):
        reg = MetricRegistry()
        h = reg.histogram("w_seconds", buckets=(1.0,), window=4).labels()
        for i in range(10):
            h.observe(float(i))
        assert list(h.window) == [6.0, 7.0, 8.0, 9.0]
        assert h.count == 10  # cumulative count is not windowed

    def test_prometheus_text_format(self):
        reg = MetricRegistry()
        reg.counter("req_total", "requests", labels=("model",)) \
            .labels(model='a"b\\c\nd').inc(3)
        reg.histogram("lat_seconds", "latency", buckets=(0.5,)).observe(0.1)
        text = reg.prometheus_text()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert r'req_total{model="a\"b\\c\nd"} 3' in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.5"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_sum 0.1" in text
        assert "lat_seconds_count 1" in text
        assert text.endswith("\n")

    def test_jsonl_round_trip(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("a_total", "help a", labels=("m",)).labels(m="x").inc(2)
        reg.gauge("b").set(-3)
        reg.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        path = str(tmp_path / "metrics.jsonl")
        reg.write_jsonl(path)
        with open(path) as f:
            parsed = MetricRegistry.parse_jsonl(f.read())
        assert parsed == reg.json_snapshot()
        assert parsed["a_total"]["samples"][0] == {
            "labels": {"m": "x"}, "value": 2}
        assert parsed["c_seconds"]["samples"][0]["count"] == 1

    def test_thread_safety_under_concurrent_writers(self):
        reg = MetricRegistry()
        c = reg.counter("n_total")
        h = reg.histogram("h_seconds", buckets=(0.5,), window=100_000)
        n_threads, n_iters = 8, 500

        def writer(tid):
            for i in range(n_iters):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        # concurrent readers must always see a parseable exposition
        for _ in range(20):
            assert "n_total" in reg.prometheus_text()
            reg.json_snapshot()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_iters
        assert h.labels().count == n_threads * n_iters
        assert h.labels().cumulative_buckets()[0][1] == n_threads * n_iters


class TestEngineStatsShared:
    def test_labels_require_registry(self):
        with pytest.raises(ValueError):
            EngineStats(labels={"model": "a"})

    def test_shared_registry_children(self):
        reg = MetricRegistry()
        a = EngineStats(registry=reg, labels={"model": "a"})
        b = EngineStats(registry=reg, labels={"model": "b"})
        a.record_batch(3, 1, 0.010)
        b.record_batch(2, 2, 0.020)
        assert a.requests == 3 and b.requests == 2
        assert a.avg_batch_fill == pytest.approx(0.75)
        assert list(a.batch_latency_s) == [0.010]
        text = reg.prometheus_text()
        assert 'serve_requests_total{model="a"} 3' in text
        assert 'serve_requests_total{model="b"} 2' in text
        snap = a.snapshot()
        assert snap["batches"] == 1
        assert snap["batch_latency_ms"]["p50"] == pytest.approx(10.0)


class TestMetricsServer:
    def test_http_exposition(self):
        reg = MetricRegistry()
        reg.counter("hits_total").inc(5)
        with start_metrics_server(reg, port=0) as server:
            assert server.port != 0
            text = urllib.request.urlopen(server.url, timeout=5).read().decode()
            assert "hits_total 5" in text
            js = urllib.request.urlopen(
                server.url + ".json", timeout=5).read().decode()
            assert json.loads(js)["hits_total"]["samples"][0]["value"] == 5
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://{server.host}:{server.port}/nope", timeout=5)

    def test_scrape_sees_live_updates(self):
        reg = MetricRegistry()
        c = reg.counter("live_total")
        with start_metrics_server(reg) as server:
            for want in (1, 2):
                c.inc()
                text = urllib.request.urlopen(server.url,
                                              timeout=5).read().decode()
                assert f"live_total {want}" in text


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_nesting_and_monotonic_clock(self):
        tr = Tracer()
        with tr.span("outer", phase="a") as outer_id:
            with tr.span("inner") as inner_id:
                pass
        spans = {s.name: s for s in tr.snapshot()}
        assert spans["inner"].parent_id == outer_id
        assert spans["outer"].parent_id is None
        assert spans["inner"].span_id == inner_id
        assert spans["outer"].attrs == {"phase": "a"}
        for s in spans.values():
            assert s.t_end_ns >= s.t_start_ns >= 0
        # inner nests strictly inside outer on the same clock
        assert spans["outer"].t_start_ns <= spans["inner"].t_start_ns
        assert spans["inner"].t_end_ns <= spans["outer"].t_end_ns
        assert tr.recorded == 2

    def test_span_recorded_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("failing"):
                raise RuntimeError("boom")
        assert [s.name for s in tr.snapshot()] == ["failing"]
        # the stack unwound: a new span is a root again
        with tr.span("after"):
            pass
        assert tr.snapshot()[-1].parent_id is None

    def test_threads_get_independent_stacks(self):
        tr = Tracer()
        done = threading.Event()

        def worker():
            with tr.span("worker-span"):
                done.wait(5)

        t = threading.Thread(target=worker, name="obs-worker")
        t.start()
        with tr.span("main-span"):
            pass
        done.set()
        t.join()
        spans = {s.name: s for s in tr.snapshot()}
        # neither thread parents the other's span
        assert spans["main-span"].parent_id is None
        assert spans["worker-span"].parent_id is None
        assert spans["worker-span"].thread == "obs-worker"

    def test_capacity_and_event_and_clear(self):
        tr = Tracer(capacity=3)
        for i in range(5):
            with tr.span("e", i=i):
                pass
        spans = tr.snapshot()
        assert len(spans) == 3 and tr.recorded == 5
        assert [s.attrs["i"] for s in spans] == [2, 3, 4]  # oldest evicted
        assert all(s.duration_ns >= 0 for s in spans)

    def test_export_jsonl_round_trip(self, tmp_path):
        tr = Tracer()
        with tr.span("a"):
            with tr.span("b", n=3):
                pass
        path = str(tmp_path / "trace.jsonl")
        assert tr.export_jsonl(path) == 2
        with open(path) as f:
            rows = [json.loads(ln) for ln in f]
        assert [r["name"] for r in rows] == ["a", "b"]  # start-ordered
        assert rows[1]["parent_id"] == rows[0]["span_id"]
        assert rows[1]["attrs"] == {"n": 3}
        assert rows[0]["duration_ns"] == (
            rows[0]["t_end_ns"] - rows[0]["t_start_ns"])

    def test_profiler_bridge(self):
        tr = Tracer(annotate=True)  # jax.profiler importable in this repo
        with tr.span("annotated"):
            pass
        assert tr.snapshot()[0].name == "annotated"

    def test_null_tracer_surface(self, tmp_path):
        with NULL_TRACER.span("x", a=1) as sid:
            assert sid == 0
        with NULL_TRACER.bind("y")(a=2) as sid:
            assert sid == 0
        assert NULL_TRACER.snapshot() == []
        path = str(tmp_path / "empty.jsonl")
        assert NULL_TRACER.export_jsonl(path) == 0
        with open(path) as f:
            assert f.read() == ""
        assert NULL_TRACER.recorded == 0


# ---------------------------------------------------------------------------
# serving integration: metrics-enabled registry + fleet
# ---------------------------------------------------------------------------


class TestServingMetrics:
    def _frozen(self, cfg, seed=0):
        from repro.infer import freeze
        state = les.create_train_state(jax.random.PRNGKey(seed), cfg)
        return freeze(state, cfg)

    def test_registry_lifecycle_metrics(self):
        from repro.serving import ModelRegistry
        cfg = tiny_cfg()
        reg = MetricRegistry()
        registry = ModelRegistry(metrics=reg)
        registry.register("m", self._frozen(cfg))
        registry.swap("m", self._frozen(cfg, seed=1))
        text = reg.prometheus_text()
        assert 'serve_model_swaps_total{model="m"} 1' in text
        assert 'serve_model_version{model="m"} 1' in text
        assert 'serve_model_events_total{event="register",model="m"} 1' in text
        assert 'serve_model_events_total{event="swap",model="m"} 1' in text
        registry.evict("m")
        assert ('serve_model_events_total{event="evict",model="m"} 1'
                in reg.prometheus_text())

    def test_fleet_queue_depth_and_batch_fill(self):
        from repro.serving import FleetEngine, ModelRegistry
        cfg = tiny_cfg()
        reg = MetricRegistry()
        registry = ModelRegistry(metrics=reg)
        registry.register("m", self._frozen(cfg))
        tracer = Tracer()
        rng = np.random.default_rng(0)
        imgs = [rng.integers(-127, 128, cfg.input_shape).astype(np.int32)
                for _ in range(6)]
        # fleet inherits the registry's metrics without an explicit arg
        with FleetEngine(registry, batch_size=4, tracer=tracer) as engine:
            assert engine.metrics is reg
            engine.classify(imgs, model="m")
        text = reg.prometheus_text()
        assert 'serve_requests_total{model="m"} 6' in text
        assert 'serve_requests_total{model="_fleet"} 6' in text
        assert 'serve_queue_depth{model="m"} 0' in text  # drained
        fill = reg.json_snapshot()["serve_batch_fill"]["samples"][0]
        assert fill["count"] >= 2  # 6 requests through batch_size 4
        names = {s.name for s in tracer.snapshot()}
        assert {"fleet.assemble", "fleet.dispatch",
                "fleet.fetch", "fleet.deliver"} <= names
        models = {s.attrs.get("model") for s in tracer.snapshot()}
        assert models == {"m"}


# ---------------------------------------------------------------------------
# CLI integration (slow: jit-compiles a real plan / training step)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestCliIntegration:
    def test_serve_cli_metrics_endpoint(self, monkeypatch, capsys, tmp_path):
        from repro.launch import serve_vision
        trace_path = str(tmp_path / "serve_trace.jsonl")
        monkeypatch.setattr("sys.argv", [
            "serve_vision", "--train-steps", "0", "--scale", "0.0625",
            "--backend", "reference", "--requests", "12", "--batch", "4",
            "--metrics-port", "0", "--trace-out", trace_path,
        ])
        serve_vision.main()
        out = capsys.readouterr().out
        # the CLI scraped its own /metrics endpoint over HTTP
        assert "[metrics] Prometheus text at http://127.0.0.1:" in out
        assert "[metrics] scraped" in out
        assert "serve_requests_total" in out
        assert "serve_queue_depth" in out
        with open(trace_path) as f:
            rows = [json.loads(ln) for ln in f]
        assert any(r["name"] == "fleet.dispatch" for r in rows)

    def test_train_cli_telemetry_jsonl(self, tmp_path):
        from repro.launch.train import train_nitro
        telem_path = str(tmp_path / "metrics.jsonl")
        trace_path = str(tmp_path / "trace.jsonl")
        result = train_nitro(
            "vgg8b", steps=4, batch=8, ckpt_dir=None, dataset="tiles32",
            scale=0.0625, telemetry_every=2, telemetry_out=telem_path,
            trace_out=trace_path,
        )
        assert result["steps"] == 4
        assert "scaled_loss" in result
        with open(telem_path) as f:
            rows = [json.loads(ln) for ln in f]
        steps = sorted({r["step"] for r in rows})
        assert steps == [0, 2]  # sampled every 2nd step
        layers = {r["layer"] for r in rows}
        assert "_opt" in layers and "output" in layers
        with open(trace_path) as f:
            names = [json.loads(ln)["name"] for ln in f]
        assert names.count("train.step") == 4
        assert "train.eval" in names


class TestBuildInfoAndHealthz:
    def test_register_build_info_is_idempotent(self):
        from repro.obs.metrics import REPRO_VERSION, register_build_info

        reg = MetricRegistry()
        register_build_info(reg, backend="cpu")
        register_build_info(reg, backend="cpu")  # safe to call again
        info = reg.gauge("repro_build_info",
                         labels=("version", "backend"))
        assert info.labels(version=REPRO_VERSION, backend="cpu").value == 1
        start = reg.gauge("process_start_time_seconds").value
        import time
        assert 0 < start <= time.time()
        text = reg.prometheus_text()
        assert f'repro_build_info{{version="{REPRO_VERSION}"' in text

    def test_healthz_endpoint(self):
        reg = MetricRegistry()
        with start_metrics_server(reg) as server:
            base = f"http://{server.host}:{server.port}"
            with urllib.request.urlopen(f"{base}/healthz") as resp:
                assert resp.status == 200
                assert resp.read() == b"ok\n"
            # and the scrape paths still answer alongside it
            with urllib.request.urlopen(f"{base}/metrics.json") as resp:
                assert resp.status == 200


class TestTracerBind:
    def test_bound_span_is_equivalent_to_span(self):
        tracer = Tracer()
        bound = tracer.bind("hot.path")
        with bound(step=1):
            pass
        with bound():  # empty attrs share one dict, must not leak attrs
            pass
        with tracer.span("hot.path", step=3):
            pass
        spans = tracer.snapshot()
        assert [s.name for s in spans] == ["hot.path"] * 3
        assert spans[0].attrs == {"step": 1}
        assert spans[1].attrs == {}
        assert spans[2].attrs == {"step": 3}

    def test_bound_span_nests_like_span(self):
        tracer = Tracer()
        inner = tracer.bind("inner")
        with tracer.span("outer") as outer_id:
            with inner() as inner_id:
                pass
        by_name = {s.name: s for s in tracer.snapshot()}
        assert by_name["inner"].parent_id == outer_id
        assert by_name["inner"].span_id == inner_id
        assert by_name["outer"].parent_id is None

    def test_null_tracer_bind_is_free(self):
        bound = NULL_TRACER.bind("x")
        with bound(step=1) as span_id:
            assert span_id == 0
        assert NULL_TRACER.snapshot() == []


class TestTrainCliHealth:
    def test_train_cli_metrics_port_and_alerts(self, tmp_path, capsys):
        from repro.launch.train import train_nitro

        alerts_path = str(tmp_path / "alerts.jsonl")
        result = train_nitro(
            "mlp1", steps=4, batch=8, ckpt_dir=None, dataset="tiles32",
            scale=0.05, telemetry_every=2,
            telemetry_out=str(tmp_path / "metrics.jsonl"),
            metrics_port=0, alerts_out=alerts_path,
        )
        assert "health" in result
        assert result["health"]["steps_observed"] == 2  # sampled steps
        assert result["straggler_events"] >= 0
        out = capsys.readouterr().out
        assert "[metrics] serving http://127.0.0.1:" in out
