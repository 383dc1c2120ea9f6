"""Pallas TPU segment-sum kernel for bucketed aggregations.

The reference aggregates doc-at-a-time into per-bucket accumulators via
``LeafBucketCollector.collect(doc, bucket)`` (search/aggregations/
bucket/BucketsAggregator.java); our XLA formulation used
``zeros(n_ords).at[ords].add(v)`` (ops/aggs.py), which TPU lowers to a
serialized scatter loop — the same pathology the scoring kernel removed
(ops/pallas_scoring.py). This kernel computes, in one device pass,

    count[o] = sum_d mask[d] * [ord[d] == o]
    total[o] = sum_d mask[d] * value[d] * [ord[d] == o]

for every bucket ordinal o, as radix-decomposed one-hot matmuls on the
MXU: with hi = ord >> 7, lo = ord & 127,

    acc[hi, lo] += onehot_hi(chunk)^T @ (onehot_lo(chunk) * v)

The grid iterates doc chunks; the (O_SUB, 128) accumulator output block
is revisited across sequential grid steps (constant index_map), so it
lives in VMEM for the whole pass and is flushed to HBM once. count+total
cover terms / histogram / value_count / sum / avg directly and feed the
engine's bucket machinery (search/aggregations.py).

Callers supply per-doc ordinals: terms aggs use the segment's ordinal
column, histograms compute ``(value - offset) // interval`` host- or
device-side first (GlobalOrdinalsStringTermsAggregator /
HistogramAggregator analogs).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticsearch_tpu.index.segment import next_pow2

LANE = 128
# docs per grid step: 8 sublane rows x 128 lanes
CHUNK_SUB = 8
CHUNK = CHUNK_SUB * LANE


def _make_segsum_kernel(o_sub: int, with_sum: bool, with_count: bool):
    def kernel(ord_ref, mask_ref, *refs):
        if with_sum:
            val_ref = refs[0]
            outs = refs[1:]
        else:
            val_ref = None
            outs = refs
        if with_count:
            out_cnt = outs[0]
            out_sum = outs[1] if with_sum else None
        else:
            out_cnt = None
            out_sum = outs[0]
        c = pl.program_id(0)

        ords = ord_ref[...]  # (CHUNK_SUB, LANE) i32
        mask = mask_ref[...] > jnp.float32(0.0)
        valid = mask & (ords >= jnp.int32(0)) \
            & (ords < jnp.int32(o_sub * LANE))
        safe = jnp.where(valid, ords, jnp.int32(0))
        hi = jnp.where(valid, lax.shift_right_logical(
            safe, jnp.int32(7)), jnp.int32(-1))
        lo = jnp.where(valid, jnp.bitwise_and(safe, jnp.int32(LANE - 1)),
                       jnp.int32(-1))
        hi_row = hi.reshape(1, CHUNK)
        lo_row = lo.reshape(1, CHUNK)
        ohT = jnp.where(
            lax.broadcasted_iota(jnp.int32, (o_sub, CHUNK), 0) == hi_row,
            jnp.float32(1.0), jnp.float32(0.0))
        # accT layout (LANE=lo, o_sub=hi): ordinal o sits at
        # [o & 127, o >> 7]
        if with_count:
            lov1 = jnp.where(
                lax.broadcasted_iota(jnp.int32, (LANE, CHUNK), 0) == lo_row,
                jnp.float32(1.0), jnp.float32(0.0))
            cnt = lax.dot_general(lov1, ohT, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

            @pl.when(c == jnp.int32(0))
            def _():
                out_cnt[...] = cnt

            @pl.when(c != jnp.int32(0))
            def _():
                out_cnt[...] = out_cnt[...] + cnt

        if with_sum:
            vals = val_ref[...]
            # two-pass error-compensated matmul (see pallas_scoring.py):
            # default bf16 MXU passes would round the metric values to 8-bit
            # mantissas; bf16-high + f32-residual summed over two DEFAULT
            # dots restores ~2^-17 rel error at 1/3 of HIGHEST's passes
            # (ohT is 0/1, bf16-exact)
            vrow = vals.reshape(1, CHUNK)
            v_hi = vrow.astype(jnp.bfloat16).astype(jnp.float32)
            v_lo = vrow - v_hi
            lane_iota = lax.broadcasted_iota(jnp.int32, (LANE, CHUNK), 0)
            lov_hi = jnp.where(lane_iota == lo_row, v_hi, jnp.float32(0.0))
            lov_lo = jnp.where(lane_iota == lo_row, v_lo, jnp.float32(0.0))
            tot = (lax.dot_general(lov_hi, ohT, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
                   + lax.dot_general(lov_lo, ohT, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32))

            @pl.when(c == jnp.int32(0))
            def _():
                out_sum[...] = tot

            @pl.when(c != jnp.int32(0))
            def _():
                out_sum[...] = out_sum[...] + tot

    return kernel


@functools.partial(jax.jit, static_argnames=("n_ords", "with_sum",
                                             "with_count", "interpret"))
def segment_aggregate(
    ords,  # [nd] int32 per-doc bucket ordinal (-1 or >= n_ords = skip)
    mask,  # [nd] float32 query-match mask (>0 = in the agg)
    values=None,  # [nd] float32 metric values (with_sum=True)
    *,
    n_ords: int,
    with_sum: bool = False,
    with_count: bool = True,
    interpret: bool = False,
):
    """Per-bucket doc counts (and value sums) in one device pass.

    Returns a tuple of (count [n_ords] f32 if with_count, total [n_ords]
    f32 if with_sum) — sum-only callers set with_count=False to skip the
    count matmul entirely. Inputs of any length are padded to a CHUNK
    multiple internally (mask pads 0, so padding never contributes).

    Accumulation is f32: counts are exact up to 2^24 contributions per
    call (the dispatchers in ops/aggs.py fall back to the int32 scatter
    path beyond that), and sums carry f32 precision.

    Non-finite metric values are sanitized (NaN -> 0, +/-inf -> +/-f32max)
    before the one-hot matmul: a raw inf would turn the 0*inf products of
    every other bucket sharing its lane into NaN. Consequence vs the
    scatter path: an inf value saturates its own bucket's sum instead of
    making it inf exactly, and NaN values are treated as missing.
    """
    assert with_sum or with_count
    nd = ords.shape[0]
    if nd == 0:
        outs = []
        if with_count:
            outs.append(jnp.zeros((n_ords,), jnp.float32))
        if with_sum:
            outs.append(jnp.zeros((n_ords,), jnp.float32))
        return tuple(outs)
    target = ((nd + CHUNK - 1) // CHUNK) * CHUNK
    if target != nd:
        ords = jnp.pad(ords, (0, target - nd))
        mask = jnp.pad(mask, (0, target - nd))
        if values is not None:
            values = jnp.pad(values, (0, target - nd))
    if values is not None:
        # clamp to the bf16-representable range: the kernel's two-pass
        # compensated matmul splits values at bf16 precision, and f32-max
        # would overflow to inf there (inf - inf = NaN poisons buckets)
        fmax = jnp.float32(float(jnp.finfo(jnp.bfloat16).max))
        # clip as well as nan_to_num: finite f32 values above bf16-max would
        # still round to inf inside the kernel's bf16 split
        values = jnp.clip(
            jnp.nan_to_num(values.astype(jnp.float32), nan=0.0,
                           posinf=fmax, neginf=-fmax), -fmax, fmax)
    n_chunks = target // CHUNK
    o_pad = next_pow2(max(n_ords, LANE))
    o_sub = o_pad // LANE

    def zero():
        return jnp.int32(0)

    in_specs = [
        pl.BlockSpec((CHUNK_SUB, LANE), lambda c: (c, zero())),
        pl.BlockSpec((CHUNK_SUB, LANE), lambda c: (c, zero())),
    ]
    operands = [ords.reshape(n_chunks * CHUNK_SUB, LANE),
                mask.reshape(n_chunks * CHUNK_SUB, LANE)]
    if with_sum:
        in_specs.append(pl.BlockSpec((CHUNK_SUB, LANE),
                                     lambda c: (c, zero())))
        operands.append(values.reshape(n_chunks * CHUNK_SUB, LANE))

    # accumulator blocks are revisited every step (constant index map) so
    # they stay resident in VMEM for the whole pass
    n_outs = int(with_count) + int(with_sum)
    out_specs = [pl.BlockSpec((LANE, o_sub), lambda c: (zero(), zero()))
                 for _ in range(n_outs)]
    out_shape = [jax.ShapeDtypeStruct((LANE, o_sub), jnp.float32)
                 for _ in range(n_outs)]

    out = pl.pallas_call(
        _make_segsum_kernel(o_sub, with_sum, with_count),
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # stated, not inherited from a Python function: the device
        # trace's readers find the custom call by this name
        name="agg_tiles",
    )(*operands)

    # accT[lo, hi] -> flat [o_pad] -> [n_ords]
    def unpack(a):
        return a.T.reshape(-1)[:n_ords]

    return tuple(unpack(a) for a in out)


def reference_segment_aggregate(ords, mask, values=None, *, n_ords):
    """Numpy oracle."""
    sel = (mask > 0) & (ords >= 0) & (ords < n_ords)
    cnt = np.zeros(n_ords, np.float32)
    np.add.at(cnt, ords[sel], 1.0)
    if values is None:
        return (cnt,)
    tot = np.zeros(n_ords, np.float32)
    np.add.at(tot, ords[sel], values[sel].astype(np.float32))
    return cnt, tot
