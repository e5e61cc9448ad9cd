"""DeepCaps (arXiv:1904.09546) on the program's normal path, at a tiny
geometry on seeded random weights: capsule cells with skips, the 3D
capsule layer's local routing with its bias, the concatenation and the
class routing.

Pinned guarantees:
  * the graph walk (`CapsPipeline._walk`) serves every face: `@pallas`
    (interpret mode) equals `@jnp` bit for bit through the whole
    pipeline, and the class capsules are not trivially zero;
  * each new layer's fake-quant face sits on its int8 grid: the SAME
    conv, the capsule add and the concat bit for bit, the conv-capsule
    layer's conv bit for bit, its squash and the 3D layer within the
    integer squash's truncation;
  * the routing bias is an exact extension: a zero bias equals the
    bias-free path, and the grouped local routing equals the class
    routing run group by group, in the kernel and the oracle;
  * a non-default variant of the local routing falls back, counted;
  * `edge.lower` refuses the pipeline at its first SAME-padded layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.edge import lower
from repro.kernels.routing import routing3d_q7_pallas, routing_q7_pallas
from repro.nn.backend import BACKENDS, PallasBackend
from repro.nn.config import DEEPCAPS_CIFAR10, DeepCapsConfig
from repro.nn.layers import (CapsAdd, CapsConcat, ConvCaps3D, PrimaryCaps,
                             QuantConv2D)
from repro.nn.pipeline import CapsPipeline
from repro.nn.plans import plan_from_json, plan_to_json
from repro.serving.sharded import wave_fn

TINY = DeepCapsConfig("deepcaps_tiny", input_shape=(32, 32, 3),
                      stem_filters=8, cells=((4, 2), (4, 4), (4, 4), (4, 4)),
                      caps_dim=6)


def _params(pipe, key):
    """The layers' own init with the conv-capsule and votes kernels
    scaled up: the squash shrinks small capsules quadratically, and at
    unit gain the last cells' capsules vanish."""
    params = pipe.init(key)
    for l in pipe.layers:
        if isinstance(l, PrimaryCaps):
            params[l.name] = dict(params[l.name], w=params[l.name]["w"] * 3)
        elif isinstance(l, ConvCaps3D):
            params[l.name] = dict(params[l.name], W=params[l.name]["W"] * 3)
    return params


@pytest.fixture(scope="module")
def tiny():
    pipe = CapsPipeline.from_deepcaps(TINY)
    params = _params(pipe, jax.random.key(3))
    x = jax.random.uniform(jax.random.key(4), (12,) + TINY.input_shape)
    with jax.default_matmul_precision("highest"):
        qnet = pipe.quantize(params, x, backend="jnp")
    return pipe, params, x, qnet


def _layer_inputs(pipe, qnet, x_q):
    """The int8 input of every layer, walked as the pipeline walks."""
    outs, last, ins = {"input": x_q}, "input", {}
    for l in pipe.layers:
        srcs = l.inputs or (last,)
        ins[l.name] = outs[srcs[0]] if len(srcs) == 1 \
            else tuple(outs[s] for s in srcs)
        outs[l.name] = l.fwd_q7(qnet.qweights[l.name], qnet.plan[l.name],
                                ins[l.name])
        last = l.name
    return ins


def _fracs(pipe, qnet, name):
    """The format(s) of a layer's input(s)."""
    names = ["input"] + [l.name for l in pipe.layers]
    srcs = pipe.layer(name).inputs or (names[names.index(name) - 1],)
    f = [qnet.plan.input_frac if s == "input" else qnet.plan[s].out_frac
         for s in srcs]
    return f[0] if len(f) == 1 else tuple(f)


def test_graph_is_the_published_structure():
    pipe = CapsPipeline.from_deepcaps(DEEPCAPS_CIFAR10)
    kinds = [type(l) for l in pipe.layers]
    assert kinds.count(PrimaryCaps) == 15 and kinds.count(CapsAdd) == 4
    assert pipe.layer("cell4").inputs == ("cell4/c", "cell4/skip3d")
    assert pipe.layer("concat").inputs == ("cell4", "cell3")
    assert isinstance(pipe.layer("cell4/skip3d"), ConvCaps3D)
    assert pipe.layer("caps").num_in == DEEPCAPS_CIFAR10.num_input_caps \
        == 2560
    shapes = jax.eval_shape(pipe.init, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 13_362_176


def test_pallas_equals_jnp_through_the_whole_pipeline(tiny):
    pipe, params, x, qnet = tiny
    x_q = qnet.quantize_input(x)
    v_jnp = np.asarray(qnet.forward(x_q))
    v_pal = np.asarray(qnet.with_backend("pallas").forward(x_q))
    np.testing.assert_array_equal(v_pal, v_jnp)
    assert v_jnp.shape == (12, 10, 6)
    assert np.count_nonzero(v_jnp) > v_jnp.size // 2
    lengths = np.asarray(qnet.class_lengths(jnp.asarray(v_jnp)))
    assert len(np.unique(np.argmax(lengths, axis=-1))) > 1


@pytest.mark.parametrize("name", ["stem", "cell1", "concat", "cell2/b"])
def test_fwd_fq_is_the_int8_graph_bit_for_bit(tiny, name):
    """The SAME conv, the capsule add, the concat and a conv-capsule
    layer's conv: the int32 sums fit fp32 exactly at these sizes, so the
    fake-quant face reproduces the int8 path bit for bit."""
    pipe, params, x, qnet = tiny
    x_q = qnet.quantize_input(x[:4])
    layer, plan = pipe.layer(name), qnet.plan[name]
    qw, p = qnet.qweights[name], params.get(name, {})
    h_q, f_in = _layer_inputs(pipe, qnet, x_q)[name], _fracs(pipe, qnet, name)
    if isinstance(layer, PrimaryCaps):
        layer, plan = layer.conv, plan.conv
        h_q = h_q.reshape(h_q.shape[:3] + (-1,))
    h = tuple(jnp.asarray(a, jnp.float32) * 2.0 ** -f
              for a, f in zip(h_q, f_in)) if isinstance(f_in, tuple) \
        else jnp.asarray(h_q, jnp.float32) * 2.0 ** -f_in
    y_fq = np.asarray(layer.fwd_fq(p, plan, h, rounding="floor"))
    y_q7 = np.asarray(layer.fwd_q7(qw, plan, h_q), np.float32)
    np.testing.assert_array_equal(y_fq, y_q7 * 2.0 ** -plan.out_frac)


@pytest.mark.parametrize("name", ["cell2/b", "cell4/skip3d"])
def test_fwd_fq_of_squashed_layers_follows_the_int8_graph(tiny, name):
    """Layers that end in the squash: the fake-quant face squashes in
    float (the straight-through surrogate), so it follows the integer
    squash (floor isqrt, floor division with 10 guard bits) to within a
    few percent of the capsules' length (1.5% and 3.5% at this
    geometry), and it follows the plan's softmax variant."""
    pipe, params, x, qnet = tiny
    x_q = qnet.quantize_input(x[:4])
    layer, plan = pipe.layer(name), qnet.plan[name]
    h_q = _layer_inputs(pipe, qnet, x_q)[name]
    h = jnp.asarray(h_q, jnp.float32) * 2.0 ** -_fracs(pipe, qnet, name)
    y_fq = np.asarray(layer.fwd_fq(params[name], plan, h))
    y_q7 = np.asarray(layer.fwd_q7(qnet.qweights[name], plan, h_q),
                      np.float32) * 2.0 ** -plan.out_frac
    assert np.linalg.norm(y_fq - y_q7) < 0.05 * np.linalg.norm(y_q7)
    if isinstance(layer, ConvCaps3D):
        y_pr = layer.fwd_fq(params[name],
                            dataclasses.replace(plan, softmax_impl="precise"),
                            h)
        assert not np.array_equal(np.asarray(y_pr), y_fq)


def _local_case(qnet, groups=6, seed=0):
    plan = qnet.plan["cell4/skip3d"]
    layer_J, layer_I = 4, 4
    rng = np.random.default_rng(seed)
    u_hat = jnp.asarray(rng.integers(-60, 61, (groups, layer_J, layer_I, 4)),
                        jnp.int8)
    bias = jnp.asarray(rng.integers(-400, 401, (layer_J, 4)), jnp.int32)
    return plan, u_hat, bias


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_routing_with_a_zero_bias_is_the_bias_free_routing(tiny, backend):
    *_, qnet = tiny
    plan, u_hat, bias = _local_case(qnet)
    be = BACKENDS[backend]
    zero = jnp.zeros_like(bias)
    want = np.asarray(be.routing_q7(u_hat, plan, rounding="floor"))
    for route in (be.routing_q7, be.local_routing_q7):
        np.testing.assert_array_equal(
            np.asarray(route(u_hat, plan, rounding="floor", bias=zero)), want)
    kw = dict(num_iters=plan.routings, caps_out_shifts=plan.caps_out_shifts,
              caps_out_fracs=plan.caps_out_fracs,
              agree_shifts=plan.agree_shifts, logit_frac=plan.logit_frac,
              rounding="floor", interpret=True)
    for kernel in (routing_q7_pallas, routing3d_q7_pallas):
        np.testing.assert_array_equal(
            np.asarray(kernel(u_hat, zero, **kw)),
            np.asarray(kernel(u_hat, **kw)))


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("groups", [1, 5, 130])
def test_local_routing_is_the_class_routing_group_by_group(tiny, groups,
                                                           with_bias):
    """The local kernel lays 128 groups on the lanes a grid step: below
    one block, an odd count and a block plus a remainder.  u_hat spans
    the whole int8 range and the bias is large, so the saturating
    shifts of s and of the agreement engage in every case."""
    *_, qnet = tiny
    plan = qnet.plan["cell4/skip3d"]
    rng = np.random.default_rng(groups)
    u_hat = jnp.asarray(rng.integers(-128, 128, (groups, 4, 4, 4)), jnp.int8)
    bias = jnp.asarray(rng.integers(-4000, 4001, (4, 4)), jnp.int32) \
        if with_bias else None
    pal = BACKENDS["pallas"]
    local = np.asarray(pal.local_routing_q7(u_hat, plan, rounding="floor",
                                            bias=bias))
    per_group = np.concatenate([
        np.asarray(pal.routing_q7(u_hat[g:g + 1], plan, rounding="floor",
                                  bias=bias)) for g in range(groups)])
    np.testing.assert_array_equal(local, per_group)
    np.testing.assert_array_equal(
        local, np.asarray(BACKENDS["jnp"].routing_q7(
            u_hat, plan, rounding="floor", bias=bias)))
    if with_bias:
        # the bias moves the answer: the comparison is not vacuous
        assert not np.array_equal(local, np.asarray(pal.local_routing_q7(
            u_hat, plan, rounding="floor")))


def test_a_non_default_local_routing_falls_back_counted(tiny):
    *_, qnet = tiny
    plan, u_hat, bias = _local_case(qnet)
    plan = dataclasses.replace(plan, softmax_impl="approx")
    be = PallasBackend()
    with pytest.warns(RuntimeWarning, match="routing3d"):
        got = be.local_routing_q7(u_hat, plan, rounding="floor", bias=bias)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(BACKENDS["jnp"].routing_q7(
            u_hat, plan, rounding="floor", bias=bias)))
    assert dict(be.fallbacks) == {("routing3d.softmax", "approx"): 1}


def test_plan_round_trips_through_json(tiny):
    *_, qnet = tiny
    assert plan_from_json(plan_to_json(qnet.plan)) == qnet.plan


def test_edge_lower_refuses_the_first_layer_it_cannot_lower(tiny):
    *_, qnet = tiny
    assert qnet.pipeline.layers[0].padding == "SAME"
    with pytest.raises(TypeError, match="'stem' .QuantConv2D."):
        lower(qnet)


def test_the_wave_names_each_layer_scope(tiny):
    """The compiled wave's op metadata lets a trace find the votes conv,
    the local routing and the class routing."""
    *_, qnet = tiny
    x = jax.ShapeDtypeStruct((2,) + TINY.input_shape, jnp.float32)
    hlo = jax.jit(wave_fn(qnet)).lower(x).compile().as_text()
    names = " ".join(set(__import__("re").findall(r'op_name="([^"]*)"',
                                                  hlo)))
    for scope in ("/stem/", "/cell1/a/", "/cell2/c/squash/",
                  "/cell4/skip3d/votes/", "/cell4/skip3d/routing/",
                  "/caps/uhat/", "/caps/routing/"):
        assert scope in names, scope


def test_new_layers_keep_the_template_faces():
    """Every new layer kind implements each face of CapsLayer."""
    from repro.nn.layers import CapsLayer
    for layer in (QuantConv2D("c", 3, 1, 2, 4, padding="SAME"),
                  PrimaryCaps("p", 3, 2, 4, 2, 4, padding="SAME", grid=True),
                  CapsAdd("a", ("x", "y")), CapsConcat("k", ("x", "y")),
                  ConvCaps3D("v", 2, 4, 3, 4)):
        assert isinstance(layer, CapsLayer)
