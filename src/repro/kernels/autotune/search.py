"""The measured tile search: candidates → parity gate → ABBA timing → cache.

``tune()`` solves one problem — one ``(op, shape, dtype, backend,
prologue)`` cache key:

1. build deterministic integer operands for the op (fixed PRNG seed —
   values don't affect timing, and integer kernels have no data-dependent
   cost);
2. enumerate VMEM-feasible candidates (``tiles.matmul_candidates`` /
   ``conv_candidates``) plus the *effective default* config — the tiles
   the dispatcher would use with no cache entry — so the winner can never
   be slower than the fallback;
3. **parity-gate**: run every candidate once and require bitwise equality
   with the reference-backend oracle (integer accumulation is order-exact,
   so any mismatch is a bug, not noise — ``ParityError``);
4. time all surviving variants in **one** ``measure.time_paired`` session
   (interleaved ABBA min-of-N), so the tuned-vs-default comparison is
   contention-robust and ``winner ≤ default`` holds by construction;
5. store the argmin in the cache (if one is given) and return
   ``(winner, measurements)``.

Ops vocabulary (shapes are the cache-key shapes):

====================  =========================  =========================
op                    shape                      dispatcher
====================  =========================  =========================
``matmul``            (M, K, N)                  ``fused_matmul``
``matmul_fwd``        (M, K, N)                  ``fused_matmul_fwd``
``matmul_grad_w``     (B, M, N)                  ``grad_w_matmul``
``matmul_grad_x``     (B, N, M)                  ``grad_x_matmul``
``conv[_fwd]``        (N, H, W, C, K, F)         ``fused_conv[_fwd]``
``conv_grad_w``       (N, H, W, C, K, F)         ``conv_grad_w``
``conv_grad_x``       (N, H, W, F, K, C)         ``conv_grad_x``
====================  =========================  =========================

Untunable combinations return ``(None, {})``: the reference matmul has no
tile knobs.  ``tune_plan`` / ``tune_training`` enumerate a whole inference
plan / training config and tune every not-yet-cached problem.

Kernel dispatchers are imported lazily inside functions: the dispatchers
import :mod:`repro.kernels.autotune.state` at module level, so an eager
import here would be circular.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .cache import TileCache, cache_key
from .measure import time_paired
from .tiles import DEFAULT_TILES, TileConfig, conv_candidates, matmul_candidates

MATMUL_OPS = ("matmul", "matmul_fwd", "matmul_grad_w", "matmul_grad_x")
CONV_OPS = ("conv", "conv_fwd", "conv_grad_w", "conv_grad_x")
GRAD_OPS = ("matmul_grad_w", "matmul_grad_x", "conv_grad_w", "conv_grad_x")


class ParityError(AssertionError):
    """A candidate tile config changed kernel *results* — never acceptable."""


def _assert_parity(got, want, op: str, tiles) -> None:
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if g.shape != w.shape or not bool((g == w).all()):
            raise ParityError(
                f"{op}: tiles {tiles} changed the result — tile choice "
                f"must be bitwise-invariant"
            )


def _rand(key, shape, dtype) -> jax.Array:
    return jax.random.randint(key, shape, -63, 64, jnp.int32).astype(dtype)


def _operands(op: str, shape, dtype: str, seed: int):
    """Deterministic integer operands for one tuning problem."""
    x_dt, w_dt = (jnp.dtype(s) for s in dtype.split(","))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    if op in ("matmul", "matmul_fwd"):
        m, k, n = shape
        return _rand(ks[0], (m, k), x_dt), _rand(ks[1], (k, n), w_dt)
    if op == "matmul_grad_w":
        b, m, n = shape  # x, delta, z_star
        return (_rand(ks[0], (b, m), x_dt), _rand(ks[1], (b, n), jnp.int32),
                _rand(ks[2], (b, n), jnp.int32))
    if op == "matmul_grad_x":
        b, n, m = shape  # delta, z_star, w
        return (_rand(ks[0], (b, n), jnp.int32),
                _rand(ks[1], (b, n), jnp.int32), _rand(ks[2], (m, n), w_dt))
    if op in ("conv", "conv_fwd"):
        n, h, w, c, k, f = shape
        return (_rand(ks[0], (n, h, w, c), x_dt),
                _rand(ks[1], (k, k, c, f), w_dt))
    if op == "conv_grad_w":
        n, h, w, c, k, f = shape  # x, delta, z_star (+k via shape)
        return (_rand(ks[0], (n, h, w, c), x_dt),
                _rand(ks[1], (n, h, w, f), jnp.int32),
                _rand(ks[2], (n, h, w, f), jnp.int32))
    if op == "conv_grad_x":
        n, h, w, f, k, c = shape  # delta, z_star, weight
        return (_rand(ks[0], (n, h, w, f), jnp.int32),
                _rand(ks[1], (n, h, w, f), jnp.int32),
                _rand(ks[2], (k, k, c, f), w_dt))
    raise ValueError(f"unknown op {op!r}; one of {MATMUL_OPS + CONV_OPS}")


def _build(op: str, operands, *, shape, backend: str, prologue: bool,
           tiles):
    """A zero-arg callable running one dispatcher variant (blocks on it)."""
    from repro.core.scaling import conv_scale_factor, linear_scale_factor
    from repro.kernels.nitro_conv import ops as conv_ops
    from repro.kernels.nitro_matmul import ops as mm_ops
    from repro.kernels.nitro_matmul.ref import masked_delta

    if op == "matmul":
        x, w = operands
        return lambda: mm_ops.fused_matmul(
            x, w, sf=linear_scale_factor(x.shape[-1]), backend=backend,
            tiles=tiles)
    if op == "matmul_fwd":
        x, w = operands
        return lambda: mm_ops.fused_matmul_fwd(
            x, w, sf=linear_scale_factor(x.shape[-1]), backend=backend,
            tiles=tiles)
    if op == "matmul_grad_w":
        x, delta, z_star = operands
        return lambda: mm_ops.grad_w_matmul(
            x, delta, z_star, backend=backend, tiles=tiles)
    if op == "matmul_grad_x":
        delta, z_star, w = operands
        return lambda: mm_ops.grad_x_matmul(
            delta, z_star, w, backend=backend, tiles=tiles)
    if op in ("conv", "conv_fwd"):
        x, w = operands
        sf = conv_scale_factor(w.shape[0], x.shape[-1])
        entry = conv_ops.fused_conv if op == "conv" else conv_ops.fused_conv_fwd
        return lambda: entry(x, w, sf=sf, backend=backend, tiles=tiles)
    if op == "conv_grad_w":
        x, delta, z_star = operands
        k = shape[4]
        if not prologue:
            delta, z_star = masked_delta(delta, z_star, 10), None
        return lambda: conv_ops.conv_grad_w(
            x, delta, kernel_size=k, z_star=z_star, backend=backend,
            tiles=tiles)
    delta, z_star, w = operands  # conv_grad_x
    if not prologue:
        delta, z_star = masked_delta(delta, z_star, 10), None
    return lambda: conv_ops.conv_grad_x(
        delta, w, z_star=z_star, backend=backend, tiles=tiles)


def _untunable(op: str, backend: str) -> bool:
    # the pure jnp matmul has no tile knobs
    return op in MATMUL_OPS and backend == "reference"


def _default_config(op: str, shape, backend: str) -> TileConfig:
    """The tiles the dispatcher uses when the cache has no entry.

    The reference streaming conv's untuned band height is
    ``conv_geometry``'s auto choice (``min(H//2, 16)``), not
    ``DEFAULT_TILES.bh`` — the probe must time what the fallback
    actually runs.
    """
    if op in CONV_OPS and backend == "reference":
        from repro.kernels.nitro_conv.ref import conv_geometry

        h, k = shape[1], shape[4]  # K sits at index 4 in both conv layouts
        bh, _, _ = conv_geometry(h, k, None, pool=False)
        return TileConfig(bh=bh)
    return DEFAULT_TILES


def _candidates(op: str, shape, dtype: str, backend: str) -> list[TileConfig]:
    itemsize = max(jnp.dtype(s).itemsize for s in dtype.split(","))
    if op in MATMUL_OPS:
        m, k, n = shape if op != "matmul_grad_w" else (
            shape[1], shape[0], shape[2])
        if op == "matmul_grad_x":
            m, k, n = shape[0], shape[2], shape[1]
        return matmul_candidates(m, k, n, itemsize=itemsize)
    if op == "conv_grad_x":
        n, h, w, f, k, c = shape
        return conv_candidates(h, w, f, k, c, itemsize=itemsize)
    n, h, w, c, k, f = shape
    cands = conv_candidates(h, w, c, k, f, itemsize=itemsize)
    if backend == "reference":
        # the jnp oracle only has the bh knob — dedup away the bf axis
        seen: dict[int, TileConfig] = {}
        for cfg in cands:
            seen.setdefault(cfg.bh, TileConfig(bh=cfg.bh))
        return list(seen.values())
    return cands


def tune(
    op: str,
    shape,
    *,
    dtype: str = "int32,int32",
    backend: str = "auto",
    prologue: bool | None = None,
    cache: TileCache | None = None,
    iters: int = 5,
    seed: int = 0,
) -> tuple[TileConfig | None, dict]:
    """Tune one problem; returns ``(winner, {config: best_us})``.

    ``(None, {})`` means the combination has no tile knobs (reference
    matmul) — the fallback is already optimal.  ``prologue`` (default:
    on for the gradient ops) tunes the grad kernel that masks δ against
    ``z_star``; off, the one fed a pre-masked δ.
    """
    from repro.kernels.nitro_matmul.ops import resolve_backend

    backend = resolve_backend(backend)
    if prologue is None:
        prologue = op in GRAD_OPS
    if _untunable(op, backend):
        return None, {}
    operands = _operands(op, shape, dtype, seed)

    configs: dict[TileConfig, object] = {}
    default = _default_config(op, shape, backend)
    for cfg in [default, *_candidates(op, shape, dtype, backend)]:
        if cfg not in configs:
            configs[cfg] = _build(
                op, operands, shape=shape, backend=backend,
                prologue=prologue, tiles=cfg)

    # Parity gate: every candidate must reproduce the reference oracle
    # bitwise before it is allowed into the timing pool.
    want = jax.block_until_ready(_build(
        op, operands, shape=shape, backend="reference",
        prologue=prologue, tiles=None)())
    for cfg, fn in configs.items():
        _assert_parity(jax.block_until_ready(fn()), want, op, cfg)

    times = time_paired(configs, iters=iters)
    winner = min(times, key=times.get)
    if cache is not None:
        cache.put(cache_key(op, shape, dtype, backend, prologue), winner)
    return winner, times


# ---------------------------------------------------------------------------
# Whole-model drivers
# ---------------------------------------------------------------------------


def plan_shapes(plan, batch: int) -> list[dict]:
    """The tuning problems an ``ExecutionPlan`` resolves at trace time.

    Mirrors ``infer.plan._execute``'s shape/dtype flow: the network input
    enters as int32, each step's output dtype is its meta's, and linear
    steps flatten whatever spatial shape precedes them.
    """
    problems = []
    shape = tuple(int(d) for d in plan.input_shape)
    act_dt = "int32"
    for w, meta in zip(plan.weights, plan.metas):
        w_dt = str(w.dtype)
        if meta.kind == "conv":
            h, w_sp, c = shape
            k, f = meta.kernel_size, int(w.shape[-1])
            problems.append(dict(
                op="conv", shape=(batch, h, w_sp, c, k, f),
                dtype=f"{act_dt},{w_dt}", prologue=False))
            shape = (h // 2, w_sp // 2, f) if meta.pool else (h, w_sp, f)
        else:
            feat = 1
            for d in shape:
                feat *= d
            problems.append(dict(
                op="matmul", shape=(batch, feat, int(w.shape[-1])),
                dtype=f"{act_dt},{w_dt}", prologue=False))
            shape = (int(w.shape[-1]),)
        act_dt = meta.out_dtype
    return problems


def training_shapes(cfg, batch: int) -> list[dict]:
    """The fused fwd/bwd kernel problems one train step resolves.

    Enumerates each block's forward (``*_fwd``) and both gradient matmuls/
    convs — the kernel-backed hot path.  (Learning/output layers run plain
    ``int_matmul``; they have no tile knobs.)  Shape flow follows
    ``core.blocks.init_block``.
    """
    problems = []
    shape = tuple(int(d) for d in cfg.input_shape)
    for spec in cfg.blocks:
        if spec.kind == "conv":
            h, w_sp, c = shape
            k, f = spec.kernel_size, spec.out_features
            problems += [
                dict(op="conv_fwd", shape=(batch, h, w_sp, c, k, f),
                     dtype="int32,int32", prologue=False),
                dict(op="conv_grad_w", shape=(batch, h, w_sp, c, k, f),
                     dtype="int32,int32", prologue=True),
                dict(op="conv_grad_x", shape=(batch, h, w_sp, f, k, c),
                     dtype="int32,int32", prologue=True),
            ]
            shape = (h // 2, w_sp // 2, f) if spec.pool else (h, w_sp, f)
        else:
            m = 1
            for d in shape:
                m *= d
            n = spec.out_features
            problems += [
                dict(op="matmul_fwd", shape=(batch, m, n),
                     dtype="int32,int32", prologue=False),
                dict(op="matmul_grad_w", shape=(batch, m, n),
                     dtype="int32,int32", prologue=True),
                dict(op="matmul_grad_x", shape=(batch, n, m),
                     dtype="int32,int32", prologue=True),
            ]
            shape = (n,)
    return problems


def _tune_problems(problems, *, backend: str, cache: TileCache,
                   iters: int, seed: int) -> dict:
    from repro.kernels.nitro_matmul.ops import resolve_backend

    backend = resolve_backend(backend)
    tuned = {}
    for p in problems:
        key = cache_key(p["op"], p["shape"], p["dtype"], backend,
                        p["prologue"])
        if key in cache:
            tuned[key] = cache.get(key)  # measurement-free: already tuned
            continue
        winner, _ = tune(
            p["op"], p["shape"], dtype=p["dtype"], backend=backend,
            prologue=p["prologue"], cache=cache,
            iters=iters, seed=seed)
        if winner is not None:
            tuned[key] = winner
    return tuned


def tune_plan(plan, batch: int, *, cache: TileCache, iters: int = 3,
              seed: int = 0) -> dict:
    """Tune every not-yet-cached problem of one inference plan.

    Returns ``{cache_key: TileConfig}`` for the tunable problems.  Run
    *before* ``compile_plan`` traces — jit bakes in the tiles it resolves.
    """
    return _tune_problems(plan_shapes(plan, batch), backend=plan.backend,
                          cache=cache, iters=iters, seed=seed)


def tune_training(cfg, batch: int, *, cache: TileCache, backend: str = "auto",
                  iters: int = 3, seed: int = 0) -> dict:
    """Tune every not-yet-cached fused fwd/bwd problem of one train config."""
    return _tune_problems(
        training_shapes(cfg, batch), backend=backend,
        cache=cache, iters=iters, seed=seed)
