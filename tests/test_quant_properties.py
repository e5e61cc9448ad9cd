"""Property-based tests (hypothesis) for the quantization framework's
invariants and the int8 numeric semantics.

hypothesis is an OPTIONAL test dependency (declared in pyproject.toml's
`test` extra); this module skips cleanly when it is not installed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional test dependency (pip install hypothesis)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.quant import int8_ops as q
from repro.quant import qformat as qf

finite_floats = st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, allow_subnormal=False)


@settings(max_examples=200, deadline=None)
@given(st.floats(min_value=1e-6, max_value=1e4, allow_nan=False))
def test_frac_bits_maximal(max_abs):
    """Alg. 7 invariant: n is the LARGEST exponent whose quantized max
    still fits in [-127, 127]."""
    n = qf.frac_bits(max_abs)
    assert round(max_abs * 2.0 ** n) <= 127
    if n < qf.MAX_FRAC_BITS:
        assert round(max_abs * 2.0 ** (n + 1)) > 127


@settings(max_examples=100, deadline=None)
@given(st.lists(finite_floats, min_size=1, max_size=64))
def test_quantize_roundtrip_bound(vals):
    """|dequant(quant(x)) - x| <= 0.5 * 2^-n for in-range x (round-to-
    nearest with power-of-two step)."""
    x = np.array(vals, np.float32)
    n = qf.frac_bits(float(np.abs(x).max()))
    deq = np.asarray(qf.dequantize(qf.quantize(x, n), n))
    assert np.all(np.abs(deq - x) <= 0.5 * 2.0 ** -n + 1e-7)


@settings(max_examples=100, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_isqrt_is_floor_sqrt(n):
    got = int(q.isqrt_newton(jnp.asarray([n], jnp.int32))[0])
    want = int(np.floor(np.sqrt(np.float64(n))))
    # guard fp edge at perfect squares
    while (want + 1) * (want + 1) <= n:
        want += 1
    while want * want > n:
        want -= 1
    assert got == want, (n, got, want)


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=2, max_value=16),
       st.integers(min_value=0, max_value=1000))
def test_softmax_q7_normalized(ncls, seed):
    """Integer softmax outputs are a Q0.7 distribution: non-negative and
    summing to ~1.0 (128), never exceeding 127 per entry."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-128, 128, (4, ncls)), jnp.int8)
    c = q.softmax_q7(x, in_frac=5)
    c = np.asarray(c, np.int32)
    assert (c >= 0).all() and (c <= 127).all()
    s = c.sum(-1)
    assert ((s >= 128 - ncls) & (s <= 128 + ncls)).all(), s


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=0, max_value=1000),
       st.integers(min_value=2, max_value=8),
       st.integers(min_value=3, max_value=9))
def test_squash_q7_norm_bounded(seed, D, in_frac):
    """squash output length <= 1.0 (i.e. ||v||_q <= 128 + rounding slack),
    and v is parallel to s (signs preserved)."""
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.integers(-128, 128, (16, D)), jnp.int8)
    v = np.asarray(q.squash_q7(s, in_frac=in_frac), np.int32)
    norm = np.sqrt((v.astype(np.int64) ** 2).sum(-1))
    assert (norm <= 130).all(), norm.max()
    sn = np.asarray(s, np.int32)
    assert ((v == 0) | (np.sign(v) == np.sign(sn))).all()


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("op", ["squash", "softmax"])
@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=1000),
       st.integers(min_value=3, max_value=9))
def test_int8_ops_axis_and_int32_forms(op, axis, seed, frac):
    """Reducing along `axis` is moving that axis last and reducing there
    (the kernels' layouts against the oracle's), and each int32 form,
    saturated and cast, is its int8 function (requantize included)."""
    q7, q32 = {"squash": (q.squash_q7, q.squash_q7_i32),
               "softmax": (q.softmax_q7, q.softmax_q7_i32)}[op]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-128, 128, (5, 6, 7)), jnp.int8)
    want = jnp.moveaxis(q7(jnp.moveaxis(x, axis, -1), frac), -1, axis)
    np.testing.assert_array_equal(q7(x, frac, axis=axis), want)
    wide = q32(x.astype(jnp.int32), frac, axis=axis)
    assert wide.dtype == jnp.int32
    np.testing.assert_array_equal(q.sat8(wide), want)
    acc = jnp.asarray(rng.integers(-2**20, 2**20, (5, 6, 7)), jnp.int32)
    for shift in (-2, 0, frac):
        for rounding in ("floor", "nearest"):
            np.testing.assert_array_equal(
                q.sat8(q.rshift_sat8_i32(acc, shift, rounding)),
                q.rshift_sat8(acc, shift, rounding))


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_matmul_q7_dequant_close_to_float(seed):
    """dequant(matmul_q7(q(a), q(b))) approximates the float product within
    the accumulated rounding bound."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(-1, 1, (8, 16)).astype(np.float32)
    b = rng.uniform(-1, 1, (16, 8)).astype(np.float32)
    na, nb = qf.frac_bits(np.abs(a).max()), qf.frac_bits(np.abs(b).max())
    ref_out = a @ b
    n_out = qf.frac_bits(np.abs(ref_out).max() + 1e-9)
    shift = qf.out_shift(na, nb, n_out)
    got = q.matmul_q7(qf.quantize(a, na), qf.quantize(b, nb), shift,
                      rounding="nearest")
    deq = np.asarray(got, np.float32) * 2.0 ** -n_out
    # error: K per-element quantization errors + one output rounding
    K = a.shape[1]
    bound = K * (2.0 ** -na + 2.0 ** -nb) * 0.75 + 2.0 ** -n_out
    assert np.abs(deq - ref_out).max() <= bound


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=1000))
def test_float_routing_coupling_sums_to_one(seed):
    """Float dynamic routing: softmax over output capsules -> for each
    input capsule the couplings sum to 1; squash keeps ||v|| < 1."""
    from repro.core.routing import dynamic_routing, squash
    rng = np.random.default_rng(seed)
    u_hat = jnp.asarray(rng.normal(0, 0.3, (2, 5, 16, 4)), jnp.float32)
    v, _ = dynamic_routing(u_hat, num_iters=3)
    norms = np.linalg.norm(np.asarray(v), axis=-1)
    assert (norms < 1.0).all()
    s = jnp.asarray(rng.normal(0, 2.0, (7, 4)))
    vs = np.linalg.norm(np.asarray(squash(s)), axis=-1)
    assert (vs < 1.0).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=1000))
def test_grad_compress_error_bound(seed):
    """One EF round: |g - decompress(compress(g))| <= step/2, and the
    error buffer equals the residual exactly."""
    from repro.optim import grad_compress as gc
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(0, 1, (32,)), jnp.float32)
    qv, e = gc.compress(g)
    deq = gc.decompress(qv, e)
    step = float(jnp.exp2(-e))
    assert float(jnp.max(jnp.abs(deq - g))) <= 0.5 * step + 1e-7


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=100))
def test_per_channel_quant_tighter_than_per_tensor(seed):
    """Beyond-paper per-channel quantization never has larger per-channel
    reconstruction error than per-tensor (property of maximal formats)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, (16, 8)).astype(np.float32)
    w[:, 0] *= 20.0                      # one loud channel
    n_t = qf.frac_bits(np.abs(w).max())
    per_t = np.asarray(qf.dequantize(qf.quantize(w, n_t), n_t))
    qc, ns = qf.quantize_per_channel(w, axis=1)
    per_c = np.asarray(qc, np.float32) * (2.0 ** -np.asarray(ns))[None, :]
    err_t = np.abs(per_t - w).max(0)
    err_c = np.abs(per_c - w).max(0)
    assert (err_c <= err_t + 1e-7).all()
