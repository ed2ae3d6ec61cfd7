"""Pallas TPU kernel for the resolver's range-ring conflict lanes.

The jnp path in ops/conflict.py checks a batch's reads against the ring
of recent committed range-writes by broadcasting to ``[Q, KR]`` (with a
W-limb lexicographic compare inside), which XLA streams through HBM as
wide intermediates. This kernel tiles the same computation through VMEM:
queries in ``TQ=128`` lanes × ring entries in ``TK`` blocks, the limb
compare unrolled over W with the ``[TQ, TK]`` running prefix kept
on-chip, and only the per-query hit bit leaving the kernel. Ref
semantics: the ring walk of ConflictSet::detectConflicts
(fdbserver/SkipList.cpp) — "does any write newer than my read version
intersect my read range".

Keys are limb-encoded uint32 (core/keys.py); lanes compare in
order-preserving signed space (x ^ 0x8000_0000 bitcast to int32) because
the VPU is an int32 machine. Inputs arrive ``[Q, W]`` row-major and are
transposed once to ``[W, Q]`` so the minor axis is the 128-lane axis.

On non-TPU backends the kernel runs in interpreter mode — bit-identical,
slow, which is exactly what the differential tests want.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # TQ: queries per block (the lane axis)


def _signed(x):
    """Order-preserving uint32 → int32 (flip the sign bit, bitcast)."""
    return jax.lax.bitcast_convert_type(
        x ^ jnp.uint32(0x80000000), jnp.int32
    )


def _pairwise_lex(a_ref, b_ref, W, TQ, TK, direction):
    """[TQ, TK] lexicographic compare between every a-column and every
    b-column: direction="lt" → a < b, "gt" → a > b. Unrolled over the W
    limbs; the eq-prefix and verdict stay in VMEM registers."""
    lt = jnp.zeros((TQ, TK), jnp.bool_)
    eq = jnp.ones((TQ, TK), jnp.bool_)
    for i in range(W):
        ai = a_ref[i, :].reshape(TQ, 1)
        bi = b_ref[i, :].reshape(1, TK)
        cmp = (ai < bi) if direction == "lt" else (ai > bi)
        lt = lt | (eq & cmp)
        eq = eq & (ai == bi)
    return lt


def _ring_kernel(point_mode, W, qlo_ref, qhi_ref, rv_ref, rb_ref, re_ref,
                 rver_ref, rmask_ref, out_ref):
    TQ = out_ref.shape[1]
    TK = rver_ref.shape[1]
    k = pl.program_id(1)

    # q starts before the write ends: q/qlo < ring_e
    before_end = _pairwise_lex(qlo_ref, re_ref, W, TQ, TK, "lt")
    if point_mode:
        # point k in [rb, re): also ¬(k < rb)
        ov = before_end & ~_pairwise_lex(qlo_ref, rb_ref, W, TQ, TK, "lt")
    else:
        # [qlo, qhi) ∩ [rb, re) ≠ ∅: also qhi > rb
        ov = before_end & _pairwise_lex(qhi_ref, rb_ref, W, TQ, TK, "gt")

    newer = rver_ref[0, :].reshape(1, TK) > rv_ref[0, :].reshape(TQ, 1)
    live = rmask_ref[0, :].reshape(1, TK) != 0
    hit = jnp.any(ov & newer & live, axis=1).astype(jnp.int32)

    @pl.when(k == 0)
    def _():
        out_ref[0, :] = jnp.zeros((TQ,), jnp.int32)

    out_ref[0, :] = jnp.maximum(out_ref[0, :], hit)


def _pad_axis(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("point_mode", "interpret", "ring_tile")
)
def ring_hits(qlo, qhi, rv, ring_b, ring_e, ring_v, ring_mask,
              point_mode=False, interpret=False, ring_tile=512):
    """Per-query ring-conflict bits.

    qlo/qhi: uint32[Q, W] query begins/ends (qhi ignored in point mode);
    rv: uint32[Q] read versions; ring_b/e: uint32[KR, W]; ring_v:
    uint32[KR]; ring_mask: bool[KR]. Returns bool[Q]: query q conflicts
    with some live ring write newer than rv[q].
    """
    Q, W = qlo.shape
    KR = ring_v.shape[0]

    qlo_t = _pad_axis(_signed(qlo).T, LANES, 1)  # [W, Qp]
    qhi_t = _pad_axis(_signed(qhi).T, LANES, 1)
    # versions get the same order-preserving sign-flip as the key limbs:
    # the jnp lanes compare uint32, and offsets may legally reach 2^31
    # before a rebase (the host threshold is policy, not a contract here)
    rv_p = _pad_axis(_signed(rv).reshape(1, Q), LANES, 1)
    tk = min(ring_tile, ((KR + LANES - 1) // LANES) * LANES)
    rb_t = _pad_axis(_signed(ring_b).T, tk, 1)  # [W, KRp]
    re_t = _pad_axis(_signed(ring_e).T, tk, 1)
    rver = _pad_axis(_signed(ring_v).reshape(1, KR), tk, 1)
    rmask = _pad_axis(ring_mask.astype(jnp.int32).reshape(1, KR), tk, 1)

    qp, krp = qlo_t.shape[1], rb_t.shape[1]
    grid = (qp // LANES, krp // tk)

    q_spec = pl.BlockSpec((W, LANES), lambda i, k: (0, i))
    r_spec = pl.BlockSpec((W, tk), lambda i, k: (0, k))
    qs_spec = pl.BlockSpec((1, LANES), lambda i, k: (0, i))
    rs_spec = pl.BlockSpec((1, tk), lambda i, k: (0, k))

    out = pl.pallas_call(
        functools.partial(_ring_kernel, point_mode, W),
        grid=grid,
        in_specs=[q_spec, q_spec, qs_spec, r_spec, r_spec, rs_spec, rs_spec],
        out_specs=pl.BlockSpec((1, LANES), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, qp), jnp.int32),
        interpret=interpret,
    )(qlo_t, qhi_t, rv_p, rb_t, re_t, rver, rmask)
    return out[0, :Q] > 0
