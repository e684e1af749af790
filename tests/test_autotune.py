"""Kernel autotuner: persistent tile cache, the search, tile-invariance
of every dispatcher, and the int8-operand MXU path.

The load-bearing invariant mirrors the kernel suites': integer
accumulation is order-exact, so *any* accepted tile configuration — and
either operand path — must be bitwise identical.  Tiling and operand
dtype are perf knobs only; these tests enforce that they can never
change a result.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _gradcheck import assert_bitwise_equal
from repro.core.activations import relu_fits_int8
from repro.core.scaling import conv_scale_factor, linear_scale_factor
from repro.kernels import autotune
from repro.kernels.autotune import (
    DEFAULT_TILES,
    TileCache,
    TileConfig,
    build_fingerprint,
    cache_key,
    configure,
    conv_candidates,
    matmul_candidates,
    plan_shapes,
    resolve_tiles,
    set_metrics,
    training_shapes,
    tune,
    tune_plan,
)
from repro.kernels.autotune.tiles import conv_vmem_bytes, matmul_vmem_bytes
from repro.kernels.grad_ops import conv_grads, linear_grads
from repro.kernels.nitro_conv.ops import fused_conv, fused_conv_fwd
from repro.kernels.nitro_matmul.ops import (
    fused_matmul,
    fused_matmul_fwd,
    resolve_operand_dtype,
)


@pytest.fixture(autouse=True)
def _no_process_cache():
    """Tests must not observe (or leak) a process-wide autotune state."""
    configure(None)
    set_metrics(None)
    yield
    configure(None)
    set_metrics(None)


def _rand(shape, dtype=jnp.int32, lo=-63, hi=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(lo, hi, shape), dtype)


def _tiny_cfg():
    """One conv + one linear block at 8x8 — the benchmark smoke topology."""
    from repro.core.blocks import BlockSpec
    from repro.core.model import NitroConfig

    return NitroConfig(
        blocks=(BlockSpec("conv", 8, pool=True, d_lr=64),
                BlockSpec("linear", 16)),
        input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
        name="tiny-smoke",
    )


# ---------------------------------------------------------------------------
# TileConfig + candidate generation
# ---------------------------------------------------------------------------


class TestTileConfig:
    def test_json_round_trip(self):
        cfg = TileConfig(bm=32, bn=256, bk=512, bh=4, bf=256)
        assert TileConfig.from_json(cfg.to_json()) == cfg

    def test_from_json_ignores_unknown_fields(self):
        assert TileConfig.from_json(
            {"bm": 64, "future_knob": 7}) == TileConfig(bm=64)

    def test_from_json_rejects_non_positive(self):
        with pytest.raises(ValueError):
            TileConfig.from_json({"bm": 0})

    def test_candidates_respect_vmem_budget(self):
        for cfg in matmul_candidates(4096, 4096, 4096):
            assert matmul_vmem_bytes(cfg.bm, cfg.bn, cfg.bk) \
                <= autotune.tiles.VMEM_BUDGET_BYTES
        for cfg in conv_candidates(64, 64, 256, 3, 256):
            assert conv_vmem_bytes(cfg.bh, cfg.bf, h=64, w=64, c=256, k=3) \
                <= autotune.tiles.VMEM_BUDGET_BYTES

    def test_default_probes_first(self):
        assert matmul_candidates(512, 512, 512)[0] == DEFAULT_TILES
        assert conv_candidates(32, 32, 64, 3, 64)[0] == DEFAULT_TILES


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


class TestTileCache:
    def test_round_trip(self, tmp_path):
        cache = TileCache(str(tmp_path))
        key = cache_key("matmul", (64, 96, 128), "int32,int32", "interpret")
        cache.put(key, TileConfig(bm=32))
        assert TileCache(str(tmp_path)).get(key) == TileConfig(bm=32)

    def test_corrupt_file_treated_as_empty(self, tmp_path):
        path = tmp_path / "tile_cache.json"
        path.write_text("{not json")
        cache = TileCache(str(path))
        assert len(cache) == 0
        cache.put("k", DEFAULT_TILES)  # and it recovers by rewriting
        assert TileCache(str(path)).get("k") == DEFAULT_TILES

    def test_stale_fingerprint_invalidates(self, tmp_path):
        path = str(tmp_path / "tile_cache.json")
        old = TileCache(path, fingerprint="repro=0.0|jax=old|backend=cpu")
        old.put("k", TileConfig(bm=32))
        fresh = TileCache(path)  # real fingerprint differs
        assert len(fresh) == 0
        assert "k" not in fresh

    def test_fingerprint_preserved_on_disk(self, tmp_path):
        path = str(tmp_path / "tile_cache.json")
        TileCache(path).put("k", DEFAULT_TILES)
        on_disk = json.loads(open(path).read())
        assert on_disk["fingerprint"] == build_fingerprint()

    def test_concurrent_writers_lose_no_entry(self, tmp_path):
        path = str(tmp_path / "tile_cache.json")

        def write(i):
            TileCache(path).put(f"k{i}", TileConfig(bm=32 + i))

        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # atomic rename + read-merge-write: every writer's entry survives
        final = TileCache(path)
        for i in range(8):
            assert final.get(f"k{i}") == TileConfig(bm=32 + i)

    def test_resolve_tiles_none_without_cache(self):
        assert resolve_tiles("matmul", (8, 8, 8), dtype="int32,int32",
                             backend="interpret") is None

    def test_resolve_tiles_hit_and_miss_counters(self, tmp_path):
        from repro.obs.metrics import MetricRegistry

        cache = TileCache(str(tmp_path))
        key = cache_key("matmul", (8, 16, 8), "int32,int32", "interpret")
        cache.put(key, TileConfig(bm=32))
        reg = MetricRegistry()
        set_metrics(reg)
        configure(cache)
        hit = resolve_tiles("matmul", (8, 16, 8), dtype="int32,int32",
                            backend="interpret")
        miss = resolve_tiles("matmul", (9, 9, 9), dtype="int32,int32",
                             backend="interpret")
        assert hit == TileConfig(bm=32) and miss is None
        snap = reg.json_snapshot()
        assert snap["kernel_tile_cache_hits_total"]["samples"][0]["value"] == 1
        assert snap["kernel_tile_cache_misses_total"]["samples"][0]["value"] == 1


# ---------------------------------------------------------------------------
# The search
# ---------------------------------------------------------------------------


class TestTune:
    def test_tune_matmul_caches_winner(self, tmp_path):
        cache = TileCache(str(tmp_path))
        winner, times = tune("matmul", (32, 64, 128), backend="interpret",
                             cache=cache, iters=1)
        assert winner in times and times[winner] == min(times.values())
        key = cache_key("matmul", (32, 64, 128), "int32,int32", "interpret")
        assert cache.get(key) == winner

    def test_tuned_never_worse_than_default_in_session(self):
        # the default probes in the SAME paired session, so the argmin is
        # <= the default's time by construction
        winner, times = tune("conv", (1, 8, 8, 3, 3, 8),
                             backend="reference", iters=1)
        assert times[winner] == min(times.values())

    def test_untunable_combinations_return_none(self):
        assert tune("matmul", (8, 8, 8), backend="reference") == (None, {})

    @pytest.mark.parametrize("op,shape", [
        ("matmul_fwd", (16, 32, 16)),
        ("matmul_grad_w", (16, 32, 16)),
        ("matmul_grad_x", (16, 32, 16)),
        ("conv_fwd", (1, 8, 8, 3, 3, 8)),
        ("conv_grad_w", (1, 8, 8, 3, 3, 8)),
        ("conv_grad_x", (1, 8, 8, 8, 3, 3)),
    ])
    def test_training_ops_tune_parity_gated(self, op, shape):
        # interpret backend: the real kernels run under every candidate,
        # and tune() itself asserts bitwise parity vs the reference oracle
        winner, times = tune(op, shape, backend="interpret", iters=1)
        assert winner in times

    def test_whole_model_shape_walkers(self):
        from repro.core import les
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = _tiny_cfg()
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        plan = compile_plan(freeze(state, cfg), backend="reference")
        probs = plan_shapes(plan, 4)
        assert len(probs) == len(plan.metas)
        assert probs[0]["op"] == "conv" and probs[0]["shape"][0] == 4
        train_probs = training_shapes(cfg, 4)
        # conv block: fwd + grad_w + grad_x; linear blocks: fwd + grads
        ops_seen = {p["op"] for p in train_probs}
        assert {"conv_fwd", "conv_grad_w", "conv_grad_x",
                "matmul_fwd", "matmul_grad_w", "matmul_grad_x"} <= ops_seen

    def test_tune_plan_second_call_measurement_free(self, tmp_path):
        from repro.core import les
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = _tiny_cfg()
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        plan = compile_plan(freeze(state, cfg), backend="reference")
        cache = TileCache(str(tmp_path))
        first = tune_plan(plan, 4, cache=cache, iters=1)
        outcomes = []
        orig = autotune.search.tune

        def spy(*a, **kw):
            out = orig(*a, **kw)
            outcomes.append(out)
            return out

        autotune.search.tune, second = spy, None
        try:
            second = tune_plan(plan, 4, cache=cache, iters=1)
        finally:
            autotune.search.tune = orig
        assert second == first
        # every tunable key is served from the cache; only untunable
        # problems reach tune(), and those return without measuring
        assert all(out == (None, {}) for out in outcomes)


# ---------------------------------------------------------------------------
# Bitwise tile-invariance of the dispatchers (the defining property)
# ---------------------------------------------------------------------------

@st.composite
def tile_cfgs(draw):
    return TileConfig(
        bm=draw(st.sampled_from([8, 32, 128, 256])),
        bn=draw(st.sampled_from([32, 128, 256])),
        bk=draw(st.sampled_from([32, 128, 512])),
        bh=draw(st.sampled_from([1, 2, 3, 8, 32])),
        bf=draw(st.sampled_from([32, 128, 256])),
    )


class TestTileInvariance:
    @given(tile_cfgs())
    @settings(max_examples=8, deadline=None)
    def test_fused_matmul_any_tiles(self, tiles):
        x, w = _rand((33, 96), seed=1), _rand((96, 64), seed=2)
        sf = linear_scale_factor(96)
        want = fused_matmul(x, w, sf=sf, backend="reference")
        got = fused_matmul(x, w, sf=sf, backend="interpret", tiles=tiles)
        assert_bitwise_equal(got, want)

    @given(tile_cfgs())
    @settings(max_examples=6, deadline=None)
    def test_fused_conv_any_tiles(self, tiles):
        x, w = _rand((2, 12, 12, 3), seed=3), _rand((3, 3, 3, 16), seed=4)
        sf = conv_scale_factor(3, 3)
        want = fused_conv(x, w, sf=sf, pool=True, backend="reference")
        for backend in ("reference", "interpret"):
            got = fused_conv(x, w, sf=sf, pool=True, backend=backend,
                             tiles=tiles)
            assert_bitwise_equal(got, want)

    @given(tile_cfgs())
    @settings(max_examples=4, deadline=None)
    def test_training_fwd_bwd_any_tiles(self, tiles):
        x, w = _rand((2, 8, 8, 3), seed=5), _rand((3, 3, 3, 8), seed=6)
        delta = _rand((2, 8, 8, 8), seed=7)
        sf = conv_scale_factor(3, 3)
        a_ref, z_ref = fused_conv_fwd(x, w, sf=sf, backend="reference")
        a, z = fused_conv_fwd(x, w, sf=sf, backend="interpret", tiles=tiles)
        assert_bitwise_equal(a, a_ref)
        assert_bitwise_equal(z, z_ref)
        gx_ref, gw_ref = conv_grads(x, w, delta, z_star=z_ref,
                                    backend="reference")
        gx, gw = conv_grads(x, w, delta, z_star=z_ref, backend="interpret",
                            tiles=tiles)
        assert_bitwise_equal(gx, gx_ref)
        assert_bitwise_equal(gw, gw_ref)

    def test_linear_grads_tiles(self):
        x, w = _rand((16, 48), seed=8), _rand((48, 32), seed=9)
        delta = _rand((16, 32), seed=10)
        _, z = fused_matmul_fwd(x, w, sf=linear_scale_factor(48),
                                backend="reference")
        want = linear_grads(x, w, delta, z_star=z, backend="reference")
        got = linear_grads(x, w, delta, z_star=z, backend="interpret",
                           tiles=TileConfig(bm=8, bn=32, bk=256))
        for g, r in zip(got, want):
            assert_bitwise_equal(g, r)

    def test_plan_logits_tile_invariant_via_cache(self, tmp_path):
        from repro.core import les, model as M
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = _tiny_cfg()
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        fm = freeze(state, cfg)
        x = _rand((4, 8, 8, 3), lo=-127, hi=128, seed=11)
        want = M.frozen_forward(state.params, cfg, x)
        cache = TileCache(str(tmp_path))
        plan = compile_plan(fm, backend="reference")
        for p in plan_shapes(plan, 4):
            if p["op"] == "conv":  # force a non-default band height
                cache.put(cache_key(p["op"], p["shape"], p["dtype"],
                                    "reference", p["prologue"]),
                          TileConfig(bh=3))
        configure(cache)
        tuned_plan = compile_plan(fm, backend="reference")
        assert_bitwise_equal(tuned_plan.logits(x), want)


# ---------------------------------------------------------------------------
# int8-operand MXU path
# ---------------------------------------------------------------------------


class TestInt8OperandPath:
    def test_resolve_operand_dtype(self):
        x8, w8 = _rand((4, 8), jnp.int8), _rand((8, 4), jnp.int8)
        x32 = _rand((4, 8))
        assert resolve_operand_dtype("auto", x8, w8) == "int8"
        assert resolve_operand_dtype("auto", x32, w8) == "int32"
        assert resolve_operand_dtype("int32", x8, w8) == "int32"
        with pytest.raises(ValueError):
            resolve_operand_dtype("int4", x8, w8)

    @pytest.mark.parametrize("backend", ["reference", "interpret"])
    def test_matmul_int8_parity(self, backend):
        x8 = _rand((32, 96), jnp.int8, -127, 128, seed=12)
        w8 = _rand((96, 64), jnp.int8, -127, 128, seed=13)
        sf = linear_scale_factor(96)
        want = fused_matmul(x8.astype(jnp.int32), w8.astype(jnp.int32),
                            sf=sf, backend="reference")
        got = fused_matmul(x8, w8, sf=sf, backend=backend,
                           operand_dtype="int8")
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("backend", ["reference", "interpret"])
    def test_conv_int8_parity(self, backend):
        x8 = _rand((2, 12, 12, 3), jnp.int8, -127, 128, seed=14)
        w8 = _rand((3, 3, 3, 16), jnp.int8, -16, 16, seed=15)
        sf = conv_scale_factor(3, 3)
        want = fused_conv(x8.astype(jnp.int32), w8.astype(jnp.int32),
                          sf=sf, pool=True, backend="reference")
        got = fused_conv(x8, w8, sf=sf, pool=True, backend=backend,
                         operand_dtype="int8")
        assert_bitwise_equal(got, want)

    def test_guard_narrows_concrete_fit(self):
        # int32-stored values that provably fit int8 are narrowed
        x = _rand((8, 16), jnp.int32, -100, 101, seed=16)
        w = _rand((16, 8), jnp.int32, -100, 101, seed=17)
        got = fused_matmul(x, w, sf=16, operand_dtype="int8",
                           backend="reference")
        want = fused_matmul(x, w, sf=16, backend="reference")
        assert_bitwise_equal(got, want)

    def test_guard_rejects_wide_values(self):
        x = jnp.full((4, 8), 1000, jnp.int32)
        w = _rand((8, 4), seed=18)
        with pytest.raises(ValueError, match="do not fit int8"):
            fused_matmul(x, w, sf=16, operand_dtype="int8",
                         backend="reference")

    def test_guard_rejects_traced_wide_operands(self):
        x, w = _rand((4, 8)), _rand((8, 4))

        @jax.jit
        def f(x, w):
            return fused_matmul(x, w, sf=16, operand_dtype="int8",
                                backend="reference")

        with pytest.raises(ValueError, match="traced"):
            f(x, w)

    def test_alpha_inv_one_edge_not_eligible(self):
        # α_inv = 1 is the NITRO-ReLU range that does NOT fit int8 —
        # the plan must keep such activations (and operands) int32
        assert not relu_fits_int8(1)
        assert all(relu_fits_int8(a) for a in (2, 3, 10, 100))


class TestPlanOperandDtype:
    def _plan_parts(self):
        from repro.core import les
        from repro.infer.export import freeze

        cfg = _tiny_cfg()
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        return state, cfg, freeze(state, cfg)

    def test_auto_selects_int8_and_matches_int32(self):
        from repro.core import model as M
        from repro.infer.plan import compile_plan

        state, cfg, fm = self._plan_parts()
        plan = compile_plan(fm, backend="reference", operand_dtype="auto")
        # first step's input is int32 (the raw image) — never eligible
        assert plan.metas[0].operand_dtype == "int32"
        assert any(m.operand_dtype == "int8" for m in plan.metas)
        assert all(r["operand_dtype"] in ("int8", "int32")
                   for r in plan.summary())
        x = _rand((4, 8, 8, 3), lo=-127, hi=128, seed=19)
        want = M.frozen_forward(state.params, cfg, x)
        assert_bitwise_equal(plan.logits(x), want)
        escape = compile_plan(fm, backend="reference",
                              operand_dtype="int32")
        assert all(m.operand_dtype == "int32" for m in escape.metas)
        assert_bitwise_equal(escape.logits(x), want)

    def test_force_int8_raises_when_nothing_eligible(self):
        from repro.core import les
        from repro.core.blocks import BlockSpec
        from repro.core.model import NitroConfig
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        # α_inv=1 everywhere: no activation narrows to int8, so no step
        # can prove the int8 operand fit
        cfg = NitroConfig(
            blocks=(BlockSpec("linear", 16, alpha_inv=1),),
            input_shape=(24,), num_classes=10, gamma_inv=512,
            name="no-int8")
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        fm = freeze(state, cfg)
        with pytest.raises(ValueError, match="no step is int8-eligible"):
            compile_plan(fm, operand_dtype="int8")
        compile_plan(fm, operand_dtype="auto")  # auto degrades gracefully

    def test_int8_gauge_per_step(self):
        from repro.infer.plan import compile_plan
        from repro.obs.metrics import MetricRegistry

        _, _, fm = self._plan_parts()
        reg = MetricRegistry()
        set_metrics(reg)
        plan = compile_plan(fm, backend="reference")
        samples = reg.json_snapshot()["kernel_int8_path_active"]["samples"]
        by_layer = {s["labels"]["layer"]: s["value"] for s in samples}
        assert by_layer == {
            f"{fm.name}/{i}": int(m.operand_dtype == "int8")
            for i, m in enumerate(plan.metas)
        }

    def test_quant_report_eligibility_matches_plan(self):
        from repro.infer.export import quantization_report
        from repro.infer.plan import compile_plan

        _, _, fm = self._plan_parts()
        plan = compile_plan(fm, backend="reference")
        report = quantization_report(fm)
        got = [l["int8_operand_eligible"] for l in report["layers"]]
        assert got == [m.operand_dtype == "int8" for m in plan.metas]
        assert report["num_int8_operand_eligible"] == sum(got)
