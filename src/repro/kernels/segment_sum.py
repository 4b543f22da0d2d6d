"""Per-row segment-sum Pallas kernel.

``out[t, b] = sum_r values[t, r] * (seg_ids[t, r] == b)``

The simulator core (``repro.core.simcore``) reduces replica occupancy
to per-(node, app) buckets; each trial ``t`` carries its own placement,
so the segment ids differ per row and a single one-hot matmul over the
batch is impossible.  This kernel tiles the (T, R) grid; for each row of
a tile it builds the transposed one-hot ``(n_pad, r_block)`` from a
sublane iota and contracts the row's values against it on the MXU
(``values_row @ onehot.T``), accumulating into the row's (1, n_pad)
output across the replica-axis grid steps.  Every slice is static, so
Mosaic lowers it; the simulator's XLA sort-plan ``bucket_sum`` is the
path off the TPU, and the tests run this kernel in interpret mode
(see ``src/repro/kernels/README.md``).

Mosaic has no float64: on the TPU the values are float32.  The
simulator's 0/1 occupancy masks sum to at most R, which float32 holds
exactly.  Interpret mode takes any float dtype.

Segment ids outside ``[0, n_segments)`` contribute nothing (the one-hot
never matches), which the padding below relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_sum"]

_LANE = 128          # TPU lane width: last dims padded to a multiple

# values_row (1, Rt) . onehot_t (n_pad, Rt), contracting the Rt axes
_NT_DIMS = (((1,), (1,)), ((), ()))


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _seg_kernel(vals_ref, ids_ref, out_ref, *, n_pad: int):
    t_block, r_block = vals_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # segment index down the sublanes; one (n_pad, r_block) one-hot per
    # row keeps fast memory at n_pad * r_block words
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (n_pad, r_block), 0)
    for t in range(t_block):
        hot = (ids_ref[t:t + 1, :] == iota_b).astype(vals_ref.dtype)
        row = jax.lax.dot_general(
            vals_ref[t:t + 1, :], hot, _NT_DIMS,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=out_ref.dtype)
        out_ref[t:t + 1, :] += row


def segment_sum(values, seg_ids, n_segments: int, *, t_block: int = 8,
                r_block: int = _LANE, interpret: bool = False):
    """Per-row bucket sums: (T, R) values + (T, R) int ids -> (T, B).

    ``interpret=True`` runs the kernel in Pallas interpret mode (tests,
    any backend); otherwise it is compiled for the TPU.
    """
    values = jnp.asarray(values)
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    if values.shape != seg_ids.shape or values.ndim != 2:
        raise ValueError(f"values {values.shape} / seg_ids "
                         f"{seg_ids.shape} must be matching (T, R)")
    T, R = values.shape
    Tp, Rp = _ceil_to(max(T, 1), t_block), _ceil_to(max(R, 1), r_block)
    n_pad = _ceil_to(n_segments, _LANE)
    if (Tp, Rp) != (T, R):
        # pad with value 0 (id 0 then contributes nothing)
        values = jnp.pad(values, ((0, Tp - T), (0, Rp - R)))
        seg_ids = jnp.pad(seg_ids, ((0, Tp - T), (0, Rp - R)))
    out = pl.pallas_call(
        functools.partial(_seg_kernel, n_pad=n_pad),
        grid=(Tp // t_block, Rp // r_block),
        in_specs=[pl.BlockSpec((t_block, r_block), lambda i, j: (i, j)),
                  pl.BlockSpec((t_block, r_block), lambda i, j: (i, j))],
        # an int32 zero: under x64 a literal 0 is an i64 block index,
        # which Mosaic refuses
        out_specs=pl.BlockSpec((t_block, n_pad),
                               lambda i, j: (i, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((Tp, n_pad), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_sum",
    )(values, seg_ids)
    return out[:T, :n_segments]
