"""Distribution substrate: sharding rules, ring collectives, gradient
compression, fault tolerance, checkpointing, data loader.

Multi-rank collective *semantics* are tested in-process with
``jax.vmap(..., axis_name=...)`` — vmap binds a named axis exactly like
shard_map does, so ring schedules built from ``ppermute`` run at any
simulated rank count without any devices (and under coverage).  Real
multi-*device* execution — shard_map over forced host devices — is
covered end-to-end by ``tests/test_data_parallel.py``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.launch.mesh import make_mesh
from repro.parallel import collectives as coll
from repro.parallel import compress
from repro.parallel import pipeline as pipe
from repro.parallel.sharding import (
    resolve,
    serve_rules,
    shard,
    train_rules,
    use_rules,
)
from repro.train import checkpoint as ckpt
from repro.train import fault_tolerance as ft


class TestShardingRules:
    def test_no_context_is_noop(self):
        x = jnp.ones((4, 4))
        y = shard(x, "batch", "embed")
        assert y is x

    def test_rule_tables_cover_model_axes(self):
        r = train_rules(multi_pod=True)
        assert r["batch"] == ("pod", "data")
        assert r["heads"] == "model"
        assert r["p_fsdp"] == "data"
        s = serve_rules()
        assert s["p_fsdp"] is None  # weights replicated over data at serve

    def test_resolve_inside_context(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        with use_rules(mesh, train_rules()):
            spec = resolve(("batch", None, "heads"))
            assert spec == jax.sharding.PartitionSpec(("data",), None, "model")

    def test_constraint_applies_in_jit(self):
        mesh = make_mesh((1, 1), ("data", "model"))

        def f(x):
            with use_rules(mesh, train_rules()):
                return shard(x * 2, "batch", "embed")

        y = jax.jit(f)(jnp.ones((4, 8)))
        np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 8)))


def _ranks(fn, stacked):
    """Run ``fn`` per-rank over ``stacked``'s leading dim with a bound
    named axis ``"r"`` — vmap's axis_name gives ppermute/psum/axis_index
    the same semantics shard_map would, minus the devices."""
    return jax.vmap(fn, axis_name="r")(stacked)


class TestRingCollectives:
    def _shmap(self, fn, n, *args):
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((n,), ("x",))
        return jax.shard_map(
            fn, mesh=mesh, in_specs=P("x"), out_specs=P("x")
        )(*args)

    def test_ring_all_reduce_single_device(self):
        x = jnp.arange(8.0)
        out = self._shmap(lambda v: coll.ring_all_reduce(v, "x"), 1, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    @pytest.mark.parametrize("n", [2, 3, 4])
    @pytest.mark.parametrize("rows", [8, 7])  # divisible and padded paths
    def test_ring_all_reduce_matches_psum(self, n, rows):
        """The planted-bug regression: every rank must end with the chunks
        in *global* order (a slot schedule finishing on slot r+1 passes a
        sum-only check but permutes the reassembled tensor)."""
        rng = np.random.default_rng(n * 100 + rows)
        x = jnp.asarray(rng.integers(-(2**20), 2**20, (n, rows, 3)), jnp.int32)
        ring = _ranks(lambda v: coll.ring_all_reduce(v, "r"), x)
        ref = _ranks(lambda v: jax.lax.psum(v, "r"), x)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))
        assert ring.dtype == ref.dtype

    @pytest.mark.parametrize("n", [2, 3, 4])
    def test_ring_reduce_scatter_rank_owns_its_chunk(self, n):
        """Rank r ends holding reduced chunk r — the by-rank contract the
        all-gather reassembly depends on."""
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.integers(-(2**20), 2**20, (n, 2 * n, 5)), jnp.int32)
        out = _ranks(lambda v: coll.ring_reduce_scatter(v, "r"), x)
        total = np.asarray(x).sum(axis=0, dtype=np.int32)      # (2n, 5)
        chunks = np.split(total, n, axis=0)                    # chunk r
        for r in range(n):
            np.testing.assert_array_equal(np.asarray(out[r]), chunks[r])

    @pytest.mark.parametrize("n", [2, 3, 4])
    def test_ring_all_gather_rank_order(self, n):
        x = jnp.asarray(
            np.arange(n * 6, dtype=np.int32).reshape(n, 2, 3)
        )
        out = _ranks(lambda v: coll.ring_all_gather(v, "r"), x)
        full = np.asarray(x).reshape(n * 2, 3)  # rank r rows at [2r, 2r+2)
        for r in range(n):
            np.testing.assert_array_equal(np.asarray(out[r]), full)

    def test_single_rank_degenerate_paths(self):
        x = jnp.arange(6, dtype=jnp.int32).reshape(3, 2)
        for fn in (coll.ring_all_reduce, coll.ring_reduce_scatter,
                   coll.ring_all_gather):
            out = _ranks(lambda v, f=fn: f(v, "r"), x[None])
            np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x))

    def test_reduce_scatter_rejects_non_divisible(self):
        x = jnp.zeros((2, 7, 3), jnp.int32)  # 7 rows over 2 ranks
        with pytest.raises(ValueError, match="not divisible"):
            _ranks(lambda v: coll.ring_reduce_scatter(v, "r"), x)


class TestGradientCompression:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.normal(0, 1e-3, (256,)), jnp.float32)}
        ef = compress.ef_init(g)
        q, s, ef = compress.compress(g, ef)
        assert q["w"].dtype == jnp.int8
        back = compress.decompress(q, s)
        err = np.abs(np.asarray(back["w"] - g["w"])).max()
        assert err <= float(s["w"]) / 2 + 1e-12  # half-ulp of the int8 grid

    def test_error_feedback_accumulates(self):
        """EF: the quantisation residual re-enters the next step — the
        *running sum* of compressed gradients tracks the true sum."""
        rng = np.random.default_rng(1)
        true_sum = np.zeros(64, np.float32)
        comp_sum = np.zeros(64, np.float32)
        g0 = {"w": jnp.zeros((64,), jnp.float32)}
        ef = compress.ef_init(g0)
        for i in range(30):
            g = rng.normal(0, 1e-4, 64).astype(np.float32)
            true_sum += g
            q, s, ef = compress.compress({"w": jnp.asarray(g)}, ef)
            comp_sum += np.asarray(compress.decompress(q, s)["w"])
        resid = np.abs(np.asarray(ef.residual["w"])).max()
        # EF invariant: |Σtrue − Σcompressed| == |residual| (bounded, no drift)
        drift = np.abs(true_sum - comp_sum).max()
        assert drift <= resid + 1e-6

    def test_integer_gradients_sum_exactly(self):
        """NITRO path: int32 gradient reduction is exact (no compression)."""
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((1,), ("pod",))
        g = {"w": jnp.asarray([2**30, -(2**30), 123], jnp.int32)}
        out = jax.shard_map(
            lambda t: compress.exact_integer_psum(t, "pod"),
            mesh=mesh, in_specs=P(), out_specs=P(),
        )(g)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(g["w"]))

    @pytest.mark.parametrize("n", [2, 3])
    def test_ef_compressed_psum_tracks_true_sum(self, n):
        """FP path across ranks: int8 payloads sum against the pmax'd
        scale; the result tracks the true cross-rank sum within one
        global-scale ulp per rank (approximate — unlike the NITRO path)."""
        rng = np.random.default_rng(n)
        shards = rng.normal(0, 1e-3, (n, 32)).astype(np.float32)
        g = {"w": jnp.asarray(shards)}
        ef = compress.ef_init({"w": g["w"][0]})

        def body(t):
            out, _ = compress.compressed_psum(t, ef, "r")
            return out

        out = _ranks(lambda v: body({"w": v}), g["w"])
        true = shards.sum(axis=0)
        # every rank agrees (payloads+scale are identical after pmax) ...
        for r in range(1, n):
            np.testing.assert_array_equal(
                np.asarray(out["w"][r]), np.asarray(out["w"][0]))
        # ... and tracks the true sum to n quantisation ulps
        _, s, _ = compress.compress(
            {"w": jnp.asarray(np.abs(shards).max(axis=0))},
            compress.ef_init({"w": g["w"][0]}))
        tol = n * float(s["w"])
        assert np.abs(np.asarray(out["w"][0]) - true).max() <= tol

    @pytest.mark.parametrize("num_limbs", [2, 3, 4])
    def test_limb_pack_roundtrip(self, num_limbs):
        """pack → (1-shard) unpack is the identity on in-range values."""
        bound = 2 ** (8 * num_limbs - 1)
        rng = np.random.default_rng(num_limbs)
        g = jnp.asarray(
            np.concatenate([
                rng.integers(-bound, bound, 61),
                [-bound, bound - 1, 0, -1, 1],
            ]), jnp.int32)
        limbs = compress.pack_int8_limbs(g, num_limbs)
        assert limbs.dtype == jnp.int8 and limbs.shape == (num_limbs, *g.shape)
        back = compress.unpack_limb_sums(limbs.astype(jnp.int32), 1)
        assert back.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(back), np.asarray(g))

    def test_pack_rejects_bad_limb_count(self):
        with pytest.raises(ValueError, match="num_limbs"):
            compress.pack_int8_limbs(jnp.zeros(3, jnp.int32), 5)

    def test_fits_limbs(self):
        g = jnp.asarray([-(2**15), 2**15 - 1], jnp.int32)
        assert bool(compress.fits_limbs(g, 2))
        assert not bool(compress.fits_limbs(g + 1, 2))
        assert bool(compress.fits_limbs(g * 1000, 4))

    @pytest.mark.parametrize("n", [2, 3, 4])
    @pytest.mark.parametrize("num_limbs", [2, 4])
    def test_nitro_compressed_psum_is_exact(self, n, num_limbs):
        """int8-limb wire ≡ plain psum, bit for bit, incl. nested trees."""
        bound = 2 ** (8 * num_limbs - 1) // n  # local range: no sum overflow
        rng = np.random.default_rng(n * 10 + num_limbs)
        tree = {
            "fw": {"w": jnp.asarray(
                rng.integers(-bound, bound, (n, 4, 3)), jnp.int32)},
            "lr": jnp.asarray(rng.integers(-bound, bound, (n, 7)), jnp.int32),
        }
        comp = _ranks(
            lambda t: compress.nitro_compressed_psum(
                t, "r", num_limbs=num_limbs), tree)
        ref = _ranks(lambda t: compress.exact_integer_psum(t, "r"), tree)
        for c, r in zip(jax.tree_util.tree_leaves(comp),
                        jax.tree_util.tree_leaves(ref)):
            assert c.dtype == r.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(c), np.asarray(r))


class TestCompressionProperties:
    """Hypothesis properties behind the bitwise-DP claim: integer sums are
    reduction-order invariant, the limb wire format is lossless, and the
    EF float path's error is bounded by its (always power-of-two) scale."""

    @given(st.integers(0, 2**31 - 1), st.integers(2, 5))
    @settings(max_examples=25, deadline=None)
    def test_integer_sum_order_invariant(self, seed, n):
        """Permuting shard order changes the reduction order; int32 sums
        (incl. wraparound) must not care — the property that lets psum,
        ring, and limb reductions disagree on schedule but never result."""
        rng = np.random.default_rng(seed)
        shards = rng.integers(-(2**28), 2**28, (n, 16)).astype(np.int32)
        perm = rng.permutation(n)
        a = _ranks(lambda v: jax.lax.psum(v, "r"), jnp.asarray(shards))
        b = _ranks(lambda v: jax.lax.psum(v, "r"), jnp.asarray(shards[perm]))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        ring = _ranks(
            lambda v: coll.ring_all_reduce(v, "r"), jnp.asarray(shards[perm]))
        np.testing.assert_array_equal(np.asarray(ring[0]), np.asarray(a[0]))

    @given(st.integers(0, 2**31 - 1), st.integers(2, 4))
    @settings(max_examples=25, deadline=None)
    def test_limb_psum_order_invariant(self, seed, n):
        rng = np.random.default_rng(seed)
        shards = rng.integers(-(2**28), 2**28, (n, 16)).astype(np.int32)
        perm = rng.permutation(n)
        a = _ranks(
            lambda v: compress.nitro_compressed_psum(v, "r"),
            jnp.asarray(shards))
        b = _ranks(
            lambda v: compress.nitro_compressed_psum(v, "r"),
            jnp.asarray(shards[perm]))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(
            np.asarray(a[0]), np.asarray(shards).sum(0, dtype=np.int32))

    @given(st.integers(0, 2**31 - 1), st.floats(1e-8, 1e3))
    @settings(max_examples=40, deadline=None)
    def test_ef_roundtrip_error_within_one_scale_ulp(self, seed, sigma):
        rng = np.random.default_rng(seed)
        g = {"w": jnp.asarray(rng.normal(0, sigma, (64,)), jnp.float32)}
        q, s, _ = compress.compress(g, compress.ef_init(g))
        back = compress.decompress(q, s)
        err = np.abs(np.asarray(back["w"]) - np.asarray(g["w"])).max()
        assert err <= float(s["w"])  # one ulp of the int8 grid

    @given(st.integers(0, 2**31 - 1), st.floats(1e-8, 1e3))
    @settings(max_examples=40, deadline=None)
    def test_ef_scale_is_power_of_two(self, seed, sigma):
        """Pow2 scales divide exactly in binary FP: dequantisation on every
        replica is bit-identical, whatever its libm."""
        rng = np.random.default_rng(seed)
        g = {"w": jnp.asarray(rng.normal(0, sigma, (32,)), jnp.float32)}
        _, s, _ = compress.compress(g, compress.ef_init(g))
        mantissa, _ = np.frexp(float(s["w"]))
        assert mantissa == 0.5  # exactly a power of two


class TestShardingHelpers:
    def test_named_sharding_resolves_rules(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        from repro.parallel.sharding import named_sharding

        ns = named_sharding(mesh, train_rules(), ("batch", "heads"))
        assert ns.spec == jax.sharding.PartitionSpec(("data",), "model")

    def test_tree_shardings_maps_axes_tuples(self):
        """Leaves are tuples-of-axis-names; containers (dicts, NamedTuples
        of tuples) are descended, not treated as leaves."""
        mesh = make_mesh((1, 1), ("data", "model"))
        from repro.parallel.sharding import tree_shardings

        logical = {"x": ("batch", None), "nested": {"w": ("heads",)}}
        out = tree_shardings(mesh, train_rules(), logical)
        assert out["x"].spec == jax.sharding.PartitionSpec(("data",), None)
        assert out["nested"]["w"].spec == jax.sharding.PartitionSpec("model")


class TestPipeline:
    """GPipe scaffolding: the sequential reference schedule and the
    stage-axis ppermute schedule must agree (vmap simulates the ranks)."""

    def test_split_microbatches(self):
        x = jnp.arange(12, dtype=jnp.int32).reshape(6, 2)
        m = pipe.split_microbatches(x, 3)
        assert m.shape == (3, 2, 2)
        np.testing.assert_array_equal(
            np.asarray(m).reshape(6, 2), np.asarray(x))

    def test_sequential_schedule_applies_all_stages(self):
        x = jnp.arange(12, dtype=jnp.int32).reshape(6, 2)
        out = pipe.pipeline_apply(
            lambda s, m: m * 2 + s, x, num_stages=3, num_micro=3)
        # ((x*2+0)*2+1)*2+2 = 8x + 4
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(x) * 8 + 4)

    @pytest.mark.parametrize("n", [2, 3])
    def test_stage_axis_schedule_matches_sequential(self, n):
        x = jnp.arange(6 * n, dtype=jnp.int32).reshape(3 * n, 2)
        ref = pipe.pipeline_apply(
            lambda s, m: m * 2, x, num_stages=n, num_micro=3)
        stacked = jnp.broadcast_to(x, (n, *x.shape))
        out = jax.vmap(
            lambda v: pipe.pipeline_apply(
                lambda s, m: m * 2, v,
                num_stages=n, num_micro=3, axis_name="r"),
            axis_name="r")(stacked)
        # completed microbatches drain through the last stage
        np.testing.assert_array_equal(np.asarray(out[n - 1]), np.asarray(ref))

    def test_bubble_fraction(self):
        assert pipe.bubble_fraction(1, 8) == 0.0
        assert pipe.bubble_fraction(4, 8) == pytest.approx(3 / 11)


class TestFaultTolerance:
    def test_straggler_detector_flags_slow_steps(self):
        det = ft.StragglerDetector(threshold=2.0, warmup_steps=3)
        for _ in range(10):
            det.record(1.0)
        assert not det.should_rebalance(1)
        assert det.record(5.0)  # straggler
        assert det.incidents == 1
        # EWMA not poisoned by the straggler
        assert det.ewma < 1.5

    def test_preemption_guard_simulation(self):
        guard = ft.PreemptionGuard(install=False)
        assert not guard.requested
        guard.simulate()
        assert guard.requested

    def test_elastic_policy_chooses_divisible_mesh(self):
        pol = ft.ElasticPolicy(model_parallel=16, global_batch=256)
        assert pol.choose_mesh_shape(256) == (16, 16)
        # lost 32 chips → 14 data slices don't divide 256 → fall to 8
        assert pol.choose_mesh_shape(224) == (8, 16)
        with pytest.raises(RuntimeError):
            pol.choose_mesh_shape(15)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
                "b": [jnp.ones((2,)), jnp.zeros((5,), jnp.bfloat16)]}
        ckpt.save(str(tmp_path), 7, tree, extra={"note": "x"})
        like = jax.tree_util.tree_map(jnp.zeros_like, tree)
        restored, step = ckpt.restore(str(tmp_path), like)
        assert step == 7
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            )

    def test_latest_ignores_partial(self, tmp_path):
        tree = {"a": jnp.ones((2,))}
        ckpt.save(str(tmp_path), 1, tree)
        # simulate a preempted writer: manifest missing
        os.makedirs(tmp_path / "step_00000002")
        (tmp_path / "LATEST").write_text("step_00000002")
        assert ckpt.latest_step(str(tmp_path)) is None  # refuses partial

    def test_async_checkpointer(self, tmp_path):
        tree = {"a": jnp.full((1000,), 3.0)}
        ac = ckpt.AsyncCheckpointer(str(tmp_path))
        ac.save(3, tree)
        ac.wait()
        restored, step = ckpt.restore(str(tmp_path), tree)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))

    def test_elastic_restore_resharding_hook(self, tmp_path):
        """Restore accepts shardings — single-device here, resharded meshes
        exercised by the dry-run; this validates the API path."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((1,), ("data",))
        tree = {"w": jnp.arange(16, dtype=jnp.float32)}
        ckpt.save(str(tmp_path), 1, tree)
        shardings = {"w": NamedSharding(mesh, P("data"))}
        restored, _ = ckpt.restore(str(tmp_path), tree, shardings=shardings)
        assert restored["w"].sharding == shardings["w"]


class TestLoader:
    def test_sharded_loader_prefetches(self):
        from repro.data.loader import ShardedLoader, synthetic_lm_generator

        gen = synthetic_lm_generator(1000, 16, global_batch=8)
        loader = ShardedLoader(
            gen, global_batch=8, process_index=0, process_count=1
        )
        b = next(loader)
        assert b["tokens"].shape == (8, 16)
        assert b["labels"].shape == (8, 16)
        # next-token alignment
        g0 = gen(0)
        np.testing.assert_array_equal(g0["tokens"][:, 1:], g0["labels"][:, :-1])
        loader.close()

    def test_local_slice_partitions_batch(self):
        from repro.data.loader import ShardedLoader, synthetic_lm_generator

        gen = synthetic_lm_generator(1000, 8, global_batch=8)
        l0 = ShardedLoader(gen, global_batch=8, process_index=0, process_count=2)
        l1 = ShardedLoader(gen, global_batch=8, process_index=1, process_count=2)
        b0, b1 = next(l0), next(l1)
        full = gen(0)
        np.testing.assert_array_equal(
            np.concatenate([b0["tokens"], b1["tokens"]]), full["tokens"]
        )
        l0.close(); l1.close()
