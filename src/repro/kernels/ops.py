"""jit'd public wrappers around the Pallas kernels: padding to tile
boundaries (zeros are exact in integer arithmetic), batching, and the
interpret-mode switch.  `interpret=None` resolves it from the platform
(`default_interpret`): compiled Mosaic kernels on a TPU, interpret mode
(the kernel body run as plain JAX ops) anywhere else.  The kernel entry
points themselves take `interpret` with no default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import q7_matmul as _q7
from repro.kernels import routing as _routing
from repro.kernels import squash as _squash
from repro.kernels import w8a8_matmul as _w8a8


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def matmul_q7(a, b, shift: int, rounding: str = "floor",
              bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool | None = None):
    """[M,K] x [K,N] int8 -> int8 (paper's mat_mult_q7; TPU tiling)."""
    interpret = default_interpret() if interpret is None else interpret
    M, N = a.shape[0], b.shape[1]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, a.shape[1])
    ap = _pad_to(a, bm_, bk_)
    bp = _pad_to(b, bk_, bn_)
    out = _q7.q7_matmul_pallas(ap, bp, shift=shift, rounding=rounding,
                               bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:M, :N]


def bmm_q7(a, b, shift: int, rounding: str = "floor",
           interpret: bool | None = None):
    """Batched [..., M, K] x [..., K, N] via vmap over the 2D kernel."""
    interpret = default_interpret() if interpret is None else interpret
    lead = a.shape[:-2]
    a2 = a.reshape((-1,) + a.shape[-2:])
    b2 = b.reshape((-1,) + b.shape[-2:])
    fn = lambda x, y: matmul_q7(x, y, shift, rounding, interpret=interpret)
    out = jax.vmap(fn)(a2, b2)
    return out.reshape(lead + out.shape[-2:])


def squash_q7(s, in_frac: int, out_frac: int = 7,
              interpret: bool | None = None):
    """[..., D] int8 -> int8 (paper Eq. 8), laid out lane-dense.

    The capsules are flattened to rows `[R, D]`, padded with zero rows
    (a zero capsule squashes to zero) to whole blocks of 128, and
    transposed to the kernel's `[D, R/128, 128]`: capsules on the lanes,
    D on a leading axis.  The result is transposed back to `[..., D]`."""
    interpret = default_interpret() if interpret is None else interpret
    lead, D = s.shape[:-1], s.shape[-1]
    s2 = s.reshape(-1, D)
    R = s2.shape[0]
    rb, grid = _squash.tiling(-(-R // _squash.LANES), D)
    pad = rb * grid * _squash.LANES - R
    if pad:
        s2 = jnp.pad(s2, ((0, pad), (0, 0)))
    st = s2.T.reshape(D, rb * grid, _squash.LANES)
    out = _squash.squash_q7_pallas(st, in_frac=in_frac, out_frac=out_frac,
                                   interpret=interpret)
    return out.reshape(D, -1).T[:R].reshape(lead + (D,))


def squash_float(s, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    lead, D = s.shape[:-1], s.shape[-1]
    s2 = s.reshape(-1, D)
    R = s2.shape[0]
    br = min(256, R)
    pad = (-R) % br
    if pad:
        s2 = jnp.pad(s2, ((0, pad), (0, 0)))
    out = _squash.squash_float_pallas(s2, block_rows=br, interpret=interpret)
    return out[:R].reshape(lead + (D,))


def routing_q7(u_hat, num_iters: int, caps_out_shifts, caps_out_fracs,
               agree_shifts, logit_frac: int, rounding: str = "floor",
               interpret: bool | None = None, bias=None, local: bool = False):
    """Fused dynamic routing: u_hat [B,J,I,O] int8 -> v [B,J,O] int8.

    `bias` (int32 [J, O], in the accumulator's format) is added to s
    before each iteration's shift.  `local=True` runs the grouped entry
    (`routing3d_q7_pallas`: B is then the number of local groups, laid
    on the lanes, 128 a grid step)."""
    interpret = default_interpret() if interpret is None else interpret
    entry = _routing.routing3d_q7_pallas if local \
        else _routing.routing_q7_pallas
    return entry(
        u_hat, bias, num_iters=num_iters,
        caps_out_shifts=tuple(caps_out_shifts),
        caps_out_fracs=tuple(caps_out_fracs),
        agree_shifts=tuple(agree_shifts), logit_frac=logit_frac,
        rounding=rounding, interpret=interpret)


def w8a8_matmul(a, w, col_shift, rounding: str = "nearest",
                interpret: bool | None = None):
    """W8A8 with per-channel shifts: [M,K] x [K,N] + [N] -> int8 [M,N]."""
    interpret = default_interpret() if interpret is None else interpret
    M, N = a.shape[0], w.shape[1]
    bm_, bn_, bk_ = min(128, M), min(128, N), min(128, a.shape[1])
    ap = _pad_to(a, bm_, bk_)
    wp = _pad_to(w, bk_, bn_)
    shp = col_shift
    p = (-N) % bn_
    if p:
        shp = jnp.pad(col_shift, (0, p))
    out = _w8a8.w8a8_matmul_pallas(ap, wp, shp, rounding=rounding,
                                   bm=bm_, bn=bn_, bk=bk_,
                                   interpret=interpret)
    return out[:M, :N]
