"""Pallas TPU kernel: FUSED int8 dynamic routing (beyond-paper, DESIGN §7).

The paper's capsule layer round-trips u_hat / b / c / v through memory
between its four support functions on every routing iteration (Alg. 5).
On TPU the whole routing state is tiny — u_hat for one sample is
J x I x O int8 (60 KB for the paper's MNIST layer) and b/c are J x I —
so the entire r-iteration loop fits in VMEM.  This kernel grids over the
batch, holds u_hat resident, and runs softmax -> weighted-sum -> squash ->
agreement entirely on-chip, eliminating (2r-1) HBM round-trips of u_hat.

The local (3D) routing of DeepCaps has many small groups instead of a
few rows with many input capsules, so `routing3d_q7_pallas` lays them
out differently: the groups on the 128 lanes, 128 a grid step.  Both
bodies call the int32 forms of repro.quant.int8_ops' requantize, softmax
and squash, reduced along their own layout's axes, so their integer
semantics are the oracle's (`nn.backend.JnpBackend.routing_q7`)
bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant import int8_ops as q

LANES = 128


def _routing_kernel(u_ref, *refs, num_iters, caps_out_shifts,
                    caps_out_fracs, agree_shifts, logit_frac, rounding):
    # I sits on the 128-wide lane axis and O on the sublanes; every
    # intermediate keeps rank 3 (keepdims) so no reduction or broadcast
    # has to move data between the two axes.  The two contractions are a
    # VPU multiply plus a reduction: at O = 4-6 they are far too narrow
    # for the MXU, and Mosaic cannot lower a batched dot in which one
    # operand has no free dimension.  `refs` is (v_ref,) or, with a bias,
    # (bias_ref, v_ref): the bias [J, O, 1] int32 is already in the
    # accumulator's format and is added to s before its shift.
    *bias_ref, v_ref = refs
    u = u_ref[0].astype(jnp.int32)              # [J, O, I] resident in VMEM
    J, O, I = u.shape
    b = jnp.zeros((J, 1, I), jnp.int32)
    v = None
    for r in range(num_iters):
        c = q.softmax_q7_i32(b, logit_frac, axis=0)              # [J, 1, I]
        s = jnp.sum(c * u, axis=2, keepdims=True)                # [J, O, 1]
        if bias_ref:
            s = s + bias_ref[0][...]
        s_q = q.rshift_sat8_i32(s, caps_out_shifts[r], rounding)
        v = q.squash_q7_i32(s_q, caps_out_fracs[r], axis=1)      # [J, O, 1]
        if r < num_iters - 1:
            a = jnp.sum(u * v, axis=1, keepdims=True)            # [J, 1, I]
            a = q.rshift_sat8_i32(a, agree_shifts[r], rounding)
            b = jnp.clip(b + a, q.INT8_MIN, q.INT8_MAX)          # q7 add
    v_ref[0] = v


_STATIC = ("num_iters", "caps_out_shifts", "caps_out_fracs", "agree_shifts",
           "logit_frac", "rounding", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def routing_q7_pallas(u_hat, bias=None, *, num_iters: int,
                      caps_out_shifts: tuple, caps_out_fracs: tuple,
                      agree_shifts: tuple, logit_frac: int, rounding: str,
                      interpret: bool):
    """u_hat int8 [B, J, I, O] -> v int8 [B, J, O], all r iterations
    fused, one sample a grid step.  `bias` (int32 [J, O], in the
    accumulator's format) is added to every s before its shift.  The
    kernel reads u_hat as [B, J, O, I], with I on the lanes (the
    transpose is an XLA op outside the kernel)."""
    B, J, I, O = u_hat.shape
    in_specs = [pl.BlockSpec((1, J, O, I), lambda b: (b, 0, 0, 0))]
    args = [jnp.swapaxes(u_hat, 2, 3)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((J, O, 1), lambda b: (0, 0, 0)))
        args.append(jnp.asarray(bias, jnp.int32).reshape(J, O, 1))
    v = pl.pallas_call(
        functools.partial(_routing_kernel, num_iters=num_iters,
                          caps_out_shifts=caps_out_shifts,
                          caps_out_fracs=caps_out_fracs,
                          agree_shifts=agree_shifts, logit_frac=logit_frac,
                          rounding=rounding),
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, J, O, 1), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, J, O, 1), jnp.int32),
        interpret=interpret,
    )(*args)
    return v[..., 0].astype(jnp.int8)


def _local_routing_kernel(u_ref, *refs, num_iters, caps_out_shifts,
                          caps_out_fracs, agree_shifts, logit_frac,
                          rounding):
    # The groups sit on the 128 lanes and J on the sublanes: u [I, O, J,
    # 128] int8, so the softmax over J reduces sublanes of dense tiles,
    # the sums over I and O add whole vregs over the leading axes, and
    # the squash's isqrt runs on [1, J, 128] for 128 groups at once.
    # u is upcast one input capsule i at a time; the logits b [I, J, 128]
    # live in the VMEM scratch `b_ref`.  `refs` is (v_ref, b_ref) or,
    # with a bias, (bias_ref, v_ref, b_ref): the bias [O, J, 1] int32 is
    # already in the accumulator's format and is added to s before its
    # shift.
    *bias_ref, v_ref, b_ref = refs
    I, O, J, Gb = u_ref.shape
    b_ref[...] = jnp.zeros(b_ref.shape, jnp.int32)

    def u_at(i):
        return u_ref[i].astype(jnp.int32)                        # [O, J, Gb]

    def weigh(i, s):
        c = q.softmax_q7_i32(b_ref[pl.ds(i, 1)], logit_frac, axis=1)
        return s + c * u_at(i)                                   # [O, J, Gb]

    v = None
    for r in range(num_iters):
        s = jax.lax.fori_loop(0, I, weigh, jnp.zeros((O, J, Gb), jnp.int32))
        if bias_ref:
            s = s + bias_ref[0][...]
        s_q = q.rshift_sat8_i32(s, caps_out_shifts[r], rounding)
        v = q.squash_q7_i32(s_q, caps_out_fracs[r], axis=0)
        if r < num_iters - 1:
            def agree(i, carry):
                a = jnp.sum(u_at(i) * v, axis=0, keepdims=True)  # [1, J, Gb]
                a = q.rshift_sat8_i32(a, agree_shifts[r], rounding)
                b = b_ref[pl.ds(i, 1)]
                b_ref[pl.ds(i, 1)] = jnp.clip(b + a, q.INT8_MIN, q.INT8_MAX)
                return carry

            jax.lax.fori_loop(0, I, agree, 0)
    v_ref[...] = v.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=_STATIC)
def routing3d_q7_pallas(u_hat, bias=None, *, num_iters: int,
                        caps_out_shifts: tuple, caps_out_fracs: tuple,
                        agree_shifts: tuple, logit_frac: int, rounding: str,
                        interpret: bool):
    """Local routing: u_hat int8 [G, J, I, O] -> v int8 [G, J, O] for G
    independent groups (one per batch row and output position), the
    same integer algorithm as `routing_q7_pallas`.  The kernel reads
    u_hat as [I, O, J, G], the groups on the lanes, 128 a grid step;
    G is padded with zero groups to a multiple of 128 and the padding is
    cut off the answer (the transposes are XLA ops outside the kernel).
    `bias` (int32 [J, O], in the accumulator's format) is added to
    every s before its shift."""
    G, J, I, O = u_hat.shape
    pad = (-G) % LANES
    u = jnp.pad(u_hat, ((0, pad), (0, 0), (0, 0), (0, 0)))
    in_specs = [pl.BlockSpec((I, O, J, LANES), lambda g: (0, 0, 0, g))]
    args = [u.transpose(2, 3, 1, 0)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((O, J, 1), lambda g: (0, 0, 0)))
        args.append(jnp.asarray(bias, jnp.int32).T.reshape(O, J, 1))
    v = pl.pallas_call(
        functools.partial(_local_routing_kernel, num_iters=num_iters,
                          caps_out_shifts=caps_out_shifts,
                          caps_out_fracs=caps_out_fracs,
                          agree_shifts=agree_shifts, logit_frac=logit_frac,
                          rounding=rounding),
        grid=((G + pad) // LANES,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((O, J, LANES), lambda g: (0, 0, g)),
        out_shape=jax.ShapeDtypeStruct((O, J, G + pad), jnp.int8),
        scratch_shapes=[pltpu.VMEM((I, J, LANES), jnp.int32)],
        interpret=interpret,
    )(*args)
    return v[..., :G].transpose(2, 1, 0)
