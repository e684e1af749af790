"""Compile a FrozenModel into a fused integer execution plan.

The plan lowers every layer onto the fused Pallas kernels: ``z`` lives in a
VMEM scratch accumulator and only the final activation is written back,
narrowed to int8 whenever the NITRO-ReLU output range fits (it always does
for α_inv ≥ 2 — the range is [⌊-127/α_inv⌋-μ, 127-μ]).  Training shares
the same kernel entry points (``kernels.nitro_matmul.ops`` /
``kernels.nitro_conv.ops``) via ``core.blocks.forward_layers``; inference
differs only in dropping the ``z_star`` cache and narrowing inter-layer
activations (see ``docs/ARCHITECTURE.md``).

    HBM traffic per layer:  unfused  M·N·(4+4+4) bytes  →  fused  M·N·1

Conv layers stream: the implicit im2col kernel stages input rows through
VMEM and never materialises the ``(N·H·W, K²·C)`` patch matrix (~K²× less
conv-input traffic), with the 2×2 max-pool folded into the kernel epilogue
for ``pool=True`` layers, so pooled convs write H/2·W/2 activations
straight away.

Backends (static at compile time):

  * ``'pallas'``     — the real TPU kernel;
  * ``'interpret'``  — the same kernel through the Pallas interpreter
                       (bit-exact off-TPU, used by the parity tests);
  * ``'reference'``  — pure-jnp composition (fast on CPU; the streaming
                       conv oracle runs the same row-band algorithm);
  * ``'auto'``       — pallas on TPU, reference elsewhere.

Every backend is bit-exact with ``model.frozen_forward`` on
the same frozen params — asserted by tests/test_infer.py and
tests/test_conv_stream.py over the paper configs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.activations import relu_fits_int8
from repro.core.numerics import INT_DTYPE
from repro.infer.export import FrozenModel
from repro.kernels.autotune import state as autotune_state
from repro.kernels.nitro_conv import ops as conv_ops
from repro.kernels.nitro_matmul import ops as nitro_ops
from repro.kernels.nitro_matmul.ops import BACKENDS  # noqa: F401 — re-export (historical public name)

#: Historical private name — the predicate now lives with the activation
#: maths in ``core.activations`` (the export/serving layers use it too).
_relu_fits_int8 = relu_fits_int8


class StepMeta(NamedTuple):
    """Static (hashable) description of one fused plan step."""

    kind: str           # 'conv' | 'linear' | 'output'
    sf: int
    alpha_inv: int
    apply_relu: bool
    pool: bool
    kernel_size: int    # conv only (0 otherwise)
    out_dtype: str      # 'int8' | 'int32' — inter-layer activation dtype
    fused_pool: bool = False  # pool folded into the conv kernel epilogue
    operand_dtype: str = "int32"  # MXU operand path: 'int8' | 'int32'


def _fused(x2, w2, meta: StepMeta, backend: str):
    """One fused matmul+scale(+relu) on 2-D operands.

    Delegates to the kernel package's shared dispatcher — the same entry
    point ``core.blocks.forward_layers`` uses for the fused training
    forward, so train and infer execute one kernel implementation.
    """
    return nitro_ops.fused_matmul(
        x2, w2, sf=meta.sf, alpha_inv=meta.alpha_inv,
        apply_relu=meta.apply_relu, out_dtype=jnp.dtype(meta.out_dtype),
        backend=backend, operand_dtype=meta.operand_dtype,
    )


def _execute(weights, x, *, metas: tuple[StepMeta, ...], backend: str):
    a = jnp.asarray(x, INT_DTYPE)
    for w, meta in zip(weights, metas):
        if meta.kind == "conv":
            # 4-D in, 4-D out: the conv dispatcher owns patch formation
            # (implicit on the streaming path) and the pool epilogue —
            # no 2-D patch-matrix reshape at this level.
            a = conv_ops.fused_conv(
                a, w, sf=meta.sf, alpha_inv=meta.alpha_inv,
                apply_relu=meta.apply_relu, pool=meta.pool,
                out_dtype=jnp.dtype(meta.out_dtype),
                backend=backend, operand_dtype=meta.operand_dtype,
            )
        else:  # 'linear' | 'output' — flatten anything spatial entering
            if a.ndim > 2:
                a = a.reshape(a.shape[0], -1)
            a = _fused(a, w, meta, backend)
    return a


class ExecutionPlan:
    """A FrozenModel lowered to fused kernel calls; jit-compiled per batch
    shape (serve with a fixed batch size to compile exactly once)."""

    def __init__(
        self,
        fm: FrozenModel,
        *,
        backend: str = "auto",
        operand_dtype: str = "auto",
    ):
        """``operand_dtype`` selects the MXU operand path per step:

        * ``'auto'``  — int8 wherever it is *provably* exact: the step's
          incoming activation is already int8-narrowed (previous layer's
          NITRO-ReLU range fit, per ``relu_fits_int8``) **and** the frozen
          weight is int8.  Everything else stays int32.
        * ``'int32'`` — the escape hatch: every step lifts to int32.
        * ``'int8'``  — force the fast path; raises if no step qualifies
          (so a misconfigured model cannot silently run all-int32).

        Bitwise result-invariant either way — int8×int8→int32 dots equal
        the lifted int32 dots exactly.
        """
        if operand_dtype not in nitro_ops.OPERAND_DTYPES:
            raise ValueError(
                f"unknown operand_dtype {operand_dtype!r}; "
                f"one of {nitro_ops.OPERAND_DTYPES}"
            )
        self.backend = nitro_ops.resolve_backend(backend)
        self.operand_dtype = operand_dtype
        self.input_shape = fm.input_shape
        self.num_classes = fm.num_classes
        self.name = fm.name
        metas = []
        act_dtype = "int32"  # _execute casts the network input to INT_DTYPE
        for i, layer in enumerate(fm.layers):
            out_dtype = (
                "int8"
                if layer.apply_relu and relu_fits_int8(layer.alpha_inv)
                else "int32"
            )
            is_conv = layer.kind == "conv"
            int8_ok = act_dtype == "int8" and str(layer.w.dtype) == "int8"
            step_od = (
                "int8" if int8_ok and operand_dtype != "int32" else "int32"
            )
            autotune_state.note_int8_path(
                f"{fm.name}/{i}", step_od == "int8"
            )
            metas.append(StepMeta(
                kind=layer.kind, sf=layer.sf, alpha_inv=layer.alpha_inv,
                apply_relu=layer.apply_relu, pool=layer.pool,
                kernel_size=layer.w.shape[0] if is_conv else 0,
                out_dtype=out_dtype,
                fused_pool=bool(is_conv and layer.pool),
                operand_dtype=step_od,
            ))
            act_dtype = out_dtype
        if operand_dtype == "int8" and not any(
            m.operand_dtype == "int8" for m in metas
        ):
            raise ValueError(
                "operand_dtype='int8': no step is int8-eligible (needs an "
                "int8-narrowed incoming activation AND an int8 weight); "
                "use 'auto' or the int32 escape hatch"
            )
        self.metas = tuple(metas)
        self.weights = [layer.w for layer in fm.layers]
        self._fn = jax.jit(functools.partial(
            _execute, metas=self.metas, backend=self.backend
        ))

    def logits(self, x) -> jax.Array:
        """(N, *input_shape) integer batch → (N, num_classes) int32 logits."""
        return self._fn(self.weights, x)

    __call__ = logits

    def predict(self, x) -> jax.Array:
        return jnp.argmax(self.logits(x), axis=-1)

    def summary(self) -> list[dict]:
        """Per-step introspection incl. per-sample HBM-traffic estimates.

        ``hbm_per_sample_bytes`` reads the step's input once and writes its
        (pooled) activation once: conv patches only ever exist as VMEM row
        bands, and the pool runs in the conv kernel's epilogue.
        """
        rows = []
        shape = tuple(int(d) for d in self.input_shape)
        in_itemsize = 4  # _execute casts the network input to int32
        for w, meta in zip(self.weights, self.metas):
            out_itemsize = jnp.dtype(meta.out_dtype).itemsize
            if meta.kind == "conv":
                h, w_sp, c = shape
                f = int(w.shape[-1])
                in_bytes = h * w_sp * c * in_itemsize
                out_shape = (h // 2, w_sp // 2, f) if meta.pool else (h, w_sp, f)
                out_bytes = out_shape[0] * out_shape[1] * f * out_itemsize
                shape = out_shape
            else:
                feat = 1
                for d in shape:
                    feat *= d
                in_bytes = feat * in_itemsize
                out_bytes = int(w.shape[-1]) * out_itemsize
                shape = (int(w.shape[-1]),)
            rows.append({
                "kind": meta.kind,
                "weight_shape": tuple(int(d) for d in w.shape),
                "weight_dtype": str(w.dtype),
                "sf": meta.sf,
                "activation_dtype": meta.out_dtype,
                "operand_dtype": meta.operand_dtype,
                "pool": meta.pool,
                "fused_pool": meta.fused_pool,
                # per output element: unfused writes z(int32) + z*(int32) +
                # act(int32); fused writes only the narrowed activation
                "hbm_bytes_per_out_elem": {
                    "unfused": 12,
                    "fused": out_itemsize,
                },
                "hbm_per_sample_bytes": int(in_bytes + out_bytes),
            })
            in_itemsize = out_itemsize
        return rows


def compile_plan(
    fm: FrozenModel,
    *,
    backend: str = "auto",
    operand_dtype: str = "auto",
) -> ExecutionPlan:
    """FrozenModel → jit-compiled fused ExecutionPlan."""
    return ExecutionPlan(fm, backend=backend, operand_dtype=operand_dtype)
