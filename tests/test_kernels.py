"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles
(repro.quant.int8_ops, the jnp backend's routing loop), swept over
shapes / shifts / rounding modes.  Integer kernels must match
BIT-EXACTLY; float kernels to allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.nn.backend import JnpBackend
from repro.nn.config import CIFAR10, MNIST, SMALLNORB
from repro.nn.plans import RoutingPlan
from repro.quant import int8_ops as q
from repro.serving import EDGE_TINY

RNG = np.random.default_rng(42)


def i8(shape):
    return jnp.asarray(RNG.integers(-128, 128, shape), jnp.int8)


@pytest.mark.parametrize("mkn", [(20, 30, 40), (128, 128, 128),
                                 (7, 257, 130), (1, 5, 3), (200, 64, 96)])
@pytest.mark.parametrize("shift", [0, 3, 9])
@pytest.mark.parametrize("rounding", ["floor", "nearest"])
def test_q7_matmul_exact(mkn, shift, rounding):
    M, K, N = mkn
    a, b = i8((M, K)), i8((K, N))
    got = ops.matmul_q7(a, b, shift, rounding)
    want = q.matmul_q7(a, b, shift, rounding)
    np.testing.assert_array_equal(got, want)


def test_q7_matmul_negative_shift():
    a, b = i8((8, 8)), i8((8, 8))
    got = ops.matmul_q7(a, b, -2)
    want = q.matmul_q7(a, b, -2)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("batch", [(), (3,), (2, 5)])
def test_bmm_q7(batch):
    a = i8(batch + (12, 20))
    b = i8(batch + (20, 8))
    got = ops.bmm_q7(a, b, 4)
    want = q.matmul_q7(a, b, 4) if not batch else None
    # oracle: einsum per batch
    acc = jnp.einsum("...mk,...kn->...mn", a.astype(jnp.int32),
                     b.astype(jnp.int32))
    want = q.rshift_sat8(acc, 4)
    np.testing.assert_array_equal(got, want)


# R not a multiple of 128, D in {4, 6, 8, 16}, and R past one block of
# the lane-dense tiling (several grid steps, padded rows)
@pytest.mark.parametrize("rd", [(100, 4), (1024, 6), (3, 8), (64, 16),
                                (129, 8), (4101, 16), (33000, 4)])
@pytest.mark.parametrize("in_frac", [3, 5, 7, 9])
def test_squash_q7_exact(rd, in_frac):
    R, D = rd
    s = i8((R, D))
    got = ops.squash_q7(s, in_frac=in_frac)
    want = q.squash_q7(s, in_frac=in_frac)
    np.testing.assert_array_equal(got, want)


# the primary capsules a served wave squashes: (bucket, I, D) for each
# geometry at buckets 1 and 64
SERVED_CAPS = {f"{name}-b{b}": (b, cfg.num_input_caps, cfg.pcap_dim)
               for name, cfg in [("mnist", MNIST), ("smallnorb", SMALLNORB),
                                 ("cifar10", CIFAR10),
                                 ("edge_tiny", EDGE_TINY)]
               for b in (1, 64)}


@pytest.mark.parametrize("fill", [None, -128, 127, 0],
                         ids=["random", "min", "max", "zero"])
@pytest.mark.parametrize("shape", [(2, 7, 11, 4), *SERVED_CAPS.values()],
                         ids=["2x7x11x4", *SERVED_CAPS])
def test_squash_q7_batched_shape(shape, fill):
    s = i8(shape) if fill is None else jnp.full(shape, fill, jnp.int8)
    got = ops.squash_q7(s, in_frac=5)
    want = q.squash_q7(s, in_frac=5)
    assert got.shape == s.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rounding", ["floor", "nearest"])
def test_routing_fused_exact(rounding):
    B, J, I, O = 3, 10, 64, 6
    u = i8((B, J, I, O))
    kw = dict(num_iters=3, caps_out_shifts=(8, 9, 9),
              caps_out_fracs=(7, 6, 6), agree_shifts=(8, 8), logit_frac=7)
    got = ops.routing_q7(u, rounding=rounding, **kw)
    plan = RoutingPlan(uhat_shift=0, logit_frac=7, caps_out_shifts=(8, 9, 9),
                       caps_out_fracs=(7, 6, 6), agree_shifts=(8, 8))
    want = JnpBackend().routing_q7(u, plan, rounding=rounding)
    np.testing.assert_array_equal(got, want)


def test_routing_fused_matches_unfused_capsule_layer():
    """The fused kernel must agree with the step-by-step int8 capsule
    layer (core.capsnet_q7.capsule_layer_q7) — the fusion is a pure perf
    change, not a semantics change."""
    from repro.core.capsnet import MNIST
    from repro.core import capsnet_q7 as cq
    import dataclasses
    cfg = dataclasses.replace(MNIST, routings=3)
    B, J, I, O, D = 2, cfg.num_classes, 32, cfg.caps_dim, cfg.pcap_dim
    W = i8((J, I, O, D))
    u = i8((B, I, D))
    shifts = {"uhat_shift": 7, "logit_frac": 7,
              "caps_out_shift_0": 9, "caps_out_frac_0": 7,
              "caps_out_shift_1": 9, "caps_out_frac_1": 7,
              "caps_out_shift_2": 9, "caps_out_frac_2": 7,
              "agree_shift_0": 8, "agree_shift_1": 8}
    model = cq.QCapsNet(cfg=cfg, weights={"caps": {"W": W}}, shifts=shifts)
    want = cq.capsule_layer_q7(model, u)
    # fused path: compute u_hat the same way, then one kernel call
    acc = jnp.einsum("jiod,bid->bjio", W.astype(jnp.int32),
                     u.astype(jnp.int32))
    u_hat = q.rshift_sat8(acc, shifts["uhat_shift"])
    got = ops.routing_q7(u_hat, num_iters=3, caps_out_shifts=(9, 9, 9),
                         caps_out_fracs=(7, 7, 7), agree_shifts=(8, 8),
                         logit_frac=7)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mkn", [(33, 65, 19), (128, 128, 128), (4, 16, 300)])
@pytest.mark.parametrize("rounding", ["floor", "nearest"])
def test_w8a8_exact(mkn, rounding):
    M, K, N = mkn
    a, w = i8((M, K)), i8((K, N))
    sh = jnp.asarray(RNG.integers(-2, 12, (N,)), jnp.int32)
    got = ops.w8a8_matmul(a, w, sh, rounding)
    want = ref.w8a8_matmul_ref(a, w, sh, rounding)
    np.testing.assert_array_equal(got, want)


def test_squash_float_close():
    s = jnp.asarray(RNG.normal(0, 1, (64, 6)), jnp.float32)
    np.testing.assert_allclose(ops.squash_float(s), ref.squash_float_ref(s),
                               atol=1e-5)


def test_isqrt_exact_floor_sqrt():
    n = jnp.asarray([0, 1, 2, 3, 4, 8, 15, 16, 17, 1023, 1024, 1 << 20,
                     (1 << 30) + 12345], jnp.int32)
    got = q.isqrt_newton(n)
    want = jnp.asarray([int(np.sqrt(float(v))) for v in n], jnp.int32)
    np.testing.assert_array_equal(got, want)
