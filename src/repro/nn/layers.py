"""Capsule-network layers implementing the three-face protocol.

Every layer is a small frozen object with one protocol (`CapsLayer`):

  fwd_f32(params, x)            -> (y, taps)   float forward; `taps` are
                                   the layer's OWN named calibration
                                   points (no global trace dict).
  plan(params, stats, in_frac)  -> LayerQuantPlan   derive the layer's
                                   Qm.n formats and shifts (Alg. 6/7).
  quantize(params, plan)        -> int8 weight dict (Alg. 7).
  fwd_q7(qweights, plan, x, *, backend, rounding) -> y   int8 execution
                                   on a selectable op backend.

`plan_tap_names()` declares exactly which stats keys `plan` reads, so the
pipeline can verify calibration completeness instead of KeyError-ing deep
inside a walk.  `inputs` names the layers (or "input") whose outputs a
layer reads; empty means the layer before it.  A layer with several
inputs (`CapsAdd`, `CapsConcat`) gets a tuple of them, and a tuple of
their formats as `in_frac`.  int8 shapes come from the data, never the
config, so the same layer objects serve ad-hoc geometries (benchmarks,
kernel tests).

Layers also carry a fourth, training-only face used by `repro.captrain`:

  fwd_fq(params, plan, x, *, rounding) -> y   fake-quantized float forward
                                   (QAT): every tensor the int8 graph
                                   would quantize is snapped onto the SAME
                                   Qm.n grid the plan prescribes, with a
                                   straight-through gradient
                                   (`qformat.fake_quant`).  Weights and
                                   the softmax couplings use the nearest
                                   quantizer (like Alg. 7); activations
                                   use the net's rounding mode so floor
                                   training sees the truncation bias of
                                   the `>> shift` requantization.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.routing import squash
from repro.nn.backend import get_backend
from repro.nn.plans import (ConvPlan, LocalRoutingPlan, MergePlan,
                            PrimaryCapsPlan, RoutingPlan, TapStats)
from repro.nn.variants import DEFAULT_SOFTMAX, DEFAULT_SQUASH, REGISTRY
from repro.quant import int8_ops as q8
from repro.quant import qformat as qf


@runtime_checkable
class CapsLayer(Protocol):
    name: str

    def init(self, key) -> dict: ...
    def fwd_f32(self, params, x) -> tuple: ...
    def plan_tap_names(self) -> tuple: ...
    def plan(self, params, stats: TapStats, in_frac: int): ...
    def quantize(self, params, plan) -> dict: ...
    def fwd_q7(self, qweights, plan, x, *, backend="jnp",
               rounding="floor"): ...
    def fwd_fq(self, params, plan, x, *, rounding="floor"): ...


def _conv(x, w, b, stride: int, padding: str = "VALID"):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y if b is None else y + b


def _weight_frac(w) -> int:
    return qf.frac_bits(float(jnp.max(jnp.abs(w))))


@dataclasses.dataclass(frozen=True)
class QuantConv2D:
    """NHWC conv + bias (+ optional relu), VALID or SAME padded, int8 via
    one accumulator shift.  Taps: "out" (pre-activation)."""
    name: str
    kernel: int
    stride: int
    in_ch: int
    out_ch: int
    relu: bool = True
    init_scale_pow: float = 2.0     # he-normal: sqrt(init_scale_pow/fan_in)
    per_channel: bool = False       # per-output-channel weight formats
    padding: str = "VALID"
    inputs: tuple = ()

    def init(self, key) -> dict:
        k, fan_in = self.kernel, self.kernel * self.kernel * self.in_ch
        return {
            "w": jax.random.normal(key, (k, k, self.in_ch, self.out_ch),
                                   jnp.float32)
            * (self.init_scale_pow / fan_in) ** 0.5,
            "b": jnp.zeros((self.out_ch,), jnp.float32),
        }

    def fwd_f32(self, params, x):
        y = _conv(x, params["w"], params["b"], self.stride, self.padding)
        taps = {"out": y}
        return (jax.nn.relu(y) if self.relu else y), taps

    def plan_tap_names(self) -> tuple:
        return (f"{self.name}.out",)

    def plan(self, params, stats: TapStats, in_frac: int) -> ConvPlan:
        f_w = _weight_frac(params["w"])
        f_b = _weight_frac(params["b"]) if params["b"].size else f_w
        f_out = qf.frac_bits(stats[f"{self.name}.out"])
        pc_w = pc_out = pc_bias = ()
        if self.per_channel:
            # the channel formats come from the same derivation the
            # quantizer uses (qformat.quantize_per_channel), so plan and
            # weights cannot disagree
            _, ns = qf.quantize_per_channel(params["w"], axis=-1)
            pc_w = tuple(int(n) for n in ns)
            pc_out = tuple(qf.out_shift(in_frac, f, f_out) for f in pc_w)
            pc_bias = tuple(qf.bias_shift(in_frac, f, f_b) for f in pc_w)
        return ConvPlan(
            in_frac=in_frac, w_frac=f_w, b_frac=f_b, out_frac=f_out,
            out_shift=qf.out_shift(in_frac, f_w, f_out),
            bias_shift=qf.bias_shift(in_frac, f_w, f_b),
            w_frac_per_channel=pc_w, out_shift_per_channel=pc_out,
            bias_shift_per_channel=pc_bias)

    def quantize(self, params, plan: ConvPlan) -> dict:
        if plan.per_channel:
            # quantize with the PLAN's channel formats (not a fresh
            # derivation) so plan edits stay consistent with the shifts
            # fwd_q7 will apply
            qw = qf.quantize_with_fracs(params["w"],
                                        plan.w_frac_per_channel, axis=-1)
        else:
            qw = qf.quantize(params["w"], plan.w_frac)
        return {"w": qw, "b": qf.quantize(params["b"], plan.b_frac)}

    def fwd_q7(self, qweights, plan: ConvPlan, x, *, backend="jnp",
               rounding="floor"):
        be = get_backend(backend)
        if plan.per_channel:
            y = be.conv2d_q7_per_channel(
                x, qweights["w"], qweights["b"],
                plan.out_shift_per_channel, plan.bias_shift_per_channel,
                stride=self.stride, rounding=rounding, padding=self.padding)
        else:
            y = be.conv2d_q7(x, qweights["w"], qweights["b"], plan.out_shift,
                             plan.bias_shift, stride=self.stride,
                             rounding=rounding, padding=self.padding)
        return be.relu_q7(y) if self.relu else y

    def fwd_fq(self, params, plan: ConvPlan, x, *, rounding="floor"):
        """Fake-quant forward mirroring fwd_q7's requantization points:
        weights/bias on their plan grids (nearest, like Alg. 7), the
        accumulator snapped to out_frac with the net's rounding."""
        if plan.per_channel:
            w = qf.fake_quant_with_fracs(params["w"],
                                         plan.w_frac_per_channel, axis=-1)
        else:
            w = qf.fake_quant(params["w"], plan.w_frac)
        b = qf.fake_quant(params["b"], plan.b_frac)
        y = qf.fake_quant(_conv(x, w, b, self.stride, self.padding),
                          plan.out_frac, rounding)
        return jax.nn.relu(y) if self.relu else y


@dataclasses.dataclass(frozen=True)
class PrimaryCaps:
    """Convolutional capsules: conv -> capsules of `dim` components ->
    squash into Q0.7.  Taps: "out" (conv pre-squash), "squashed".

    The template's primary capsules (paper §3.3) flatten the grid to
    [B, N_caps, dim]; with `grid=True` the layer keeps it as
    [B, H', W', caps, dim] (DeepCaps' conv-capsule layer, SAME padded).
    An input capsule grid [B, H, W, caps, dim] is read as its
    caps * dim channels.  The conv faces delegate to an inner
    QuantConv2D (no relu, 1/fan_in init); this layer adds only the
    reshape + integer squash."""
    name: str
    kernel: int
    stride: int
    in_ch: int
    caps: int
    dim: int
    per_channel: bool = False
    squash_impl: str = DEFAULT_SQUASH   # variant default carried into plan
    padding: str = "VALID"
    grid: bool = False
    inputs: tuple = ()

    @property
    def out_ch(self) -> int:
        return self.caps * self.dim

    @property
    def conv(self) -> QuantConv2D:
        return QuantConv2D(self.name, self.kernel, self.stride, self.in_ch,
                           self.out_ch, relu=False, init_scale_pow=1.0,
                           per_channel=self.per_channel,
                           padding=self.padding)

    def _capsules(self, y):
        shape = y.shape[:3] + (self.caps,) if self.grid else (y.shape[0], -1)
        return y.reshape(shape + (self.dim,))

    @staticmethod
    def _channels(x):
        return x.reshape(x.shape[:3] + (-1,)) if x.ndim == 5 else x

    def init(self, key) -> dict:
        return self.conv.init(key)

    def fwd_f32(self, params, x):
        y, taps = self.conv.fwd_f32(params, self._channels(x))
        u = squash(self._capsules(y), axis=-1)
        return u, {**taps, "squashed": u}

    def plan_tap_names(self) -> tuple:
        return self.conv.plan_tap_names()

    def plan(self, params, stats: TapStats, in_frac: int) -> PrimaryCapsPlan:
        return PrimaryCapsPlan(conv=self.conv.plan(params, stats, in_frac),
                               squash_impl=self.squash_impl)

    def quantize(self, params, plan: PrimaryCapsPlan) -> dict:
        return self.conv.quantize(params, plan.conv)

    def fwd_q7(self, qweights, plan: PrimaryCapsPlan, x, *, backend="jnp",
               rounding="floor"):
        y = self.conv.fwd_q7(qweights, plan.conv, self._channels(x),
                             backend=backend, rounding=rounding)
        with jax.named_scope("squash"):
            return get_backend(backend).squash_q7(
                self._capsules(y), in_frac=plan.conv.out_frac,
                out_frac=plan.squash_out_frac, impl=plan.squash_impl)

    def fwd_fq(self, params, plan: PrimaryCapsPlan, x, *, rounding="floor"):
        y = self.conv.fwd_fq(params, plan.conv, self._channels(x),
                             rounding=rounding)
        return REGISTRY.get("squash", plan.squash_impl).fq(
            self._capsules(y), plan.squash_out_frac, rounding)


@dataclasses.dataclass(frozen=True)
class CapsAdd:
    """The sum of two capsule tensors of one shape (DeepCaps' skip),
    requantized into its own format and not squashed.  Each input is
    shifted into the output format first (`int8_ops.add_q7`: floor
    shifts in both rounding modes), then the sum saturates.  Taps:
    "out"."""
    name: str
    inputs: tuple

    def init(self, key) -> dict:
        return {}

    def fwd_f32(self, params, xs):
        y = xs[0] + xs[1]
        return y, {"out": y}

    def plan_tap_names(self) -> tuple:
        return (f"{self.name}.out",)

    def plan(self, params, stats: TapStats, in_frac: tuple) -> MergePlan:
        f_out = qf.frac_bits(stats[f"{self.name}.out"])
        return MergePlan(in_fracs=tuple(in_frac), out_frac=f_out,
                         shifts=tuple(f - f_out for f in in_frac))

    def quantize(self, params, plan: MergePlan) -> dict:
        return {}

    def fwd_q7(self, qweights, plan: MergePlan, xs, *, backend="jnp",
               rounding="floor"):
        return q8.add_q7(xs[0], xs[1], *plan.shifts)

    def fwd_fq(self, params, plan: MergePlan, xs, *, rounding="floor"):
        # a right shift floors onto the output grid; a left shift is exact
        terms = [qf.fake_quant(x, plan.out_frac, "floor") if sh > 0 else x
                 for x, sh in zip(xs, plan.shifts)]
        return qf.fake_quant(terms[0] + terms[1], plan.out_frac, "floor")


@dataclasses.dataclass(frozen=True)
class CapsConcat:
    """Capsules of several layers as one list [B, N_1 + N_2 + ..., D]
    (each input flattened to [B, N_k, D], in the order of `inputs`), in
    the coarser of the input formats: the format Alg. 7 gives the
    concatenation's calibrated max when each input's format comes from
    its own.  Taps: none."""
    name: str
    inputs: tuple

    def init(self, key) -> dict:
        return {}

    @staticmethod
    def _join(xs):
        return jnp.concatenate(
            [x.reshape(x.shape[0], -1, x.shape[-1]) for x in xs], axis=1)

    def fwd_f32(self, params, xs):
        return self._join(xs), {}

    def plan_tap_names(self) -> tuple:
        return ()

    def plan(self, params, stats: TapStats, in_frac: tuple) -> MergePlan:
        f_out = min(in_frac)
        return MergePlan(in_fracs=tuple(in_frac), out_frac=f_out,
                         shifts=tuple(f - f_out for f in in_frac))

    def quantize(self, params, plan: MergePlan) -> dict:
        return {}

    def fwd_q7(self, qweights, plan: MergePlan, xs, *, backend="jnp",
               rounding="floor"):
        return self._join([q8.rshift_sat8(x, sh, rounding)
                           for x, sh in zip(xs, plan.shifts)])

    def fwd_fq(self, params, plan: MergePlan, xs, *, rounding="floor"):
        return self._join([qf.fake_quant(x, plan.out_frac, rounding)
                           for x in xs])


@dataclasses.dataclass(frozen=True)
class CapsuleRouting:
    """Class capsules with dynamic routing (Alg. 5).  Taps: "u_hat",
    per-iteration "s/{r}", "agree/{r}", "logits/{r}"."""
    name: str
    num_out: int                    # J (classes)
    num_in: int                     # I (input capsules)
    out_dim: int                    # O
    in_dim: int                     # D
    routings: int = 3
    softmax_impl: str = DEFAULT_SOFTMAX   # variant defaults carried into
    squash_impl: str = DEFAULT_SQUASH     # the plan (registry-validated)
    per_channel: bool = False       # per-output-capsule W formats
    inputs: tuple = ()

    def init(self, key) -> dict:
        return {"W": jax.random.normal(
            key, (self.num_out, self.num_in, self.out_dim, self.in_dim),
            jnp.float32) * 0.1}

    def fwd_f32(self, params, u):
        u_hat = jnp.einsum("jiod,bid->bjio", params["W"], u)
        return _route_f32(u_hat, self.routings)

    def plan_tap_names(self) -> tuple:
        return _routing_tap_names(self.name, self.routings)

    def plan(self, params, stats: TapStats, in_frac: int) -> RoutingPlan:
        f_W = _weight_frac(params["W"])
        fields = _routing_fields(self.name, self.routings, stats, in_frac,
                                 f_W)
        f_uhat = fields["uhat_frac"]
        pc_W = pc_shift = ()
        if self.per_channel:
            # per-output-capsule formats from the same derivation the
            # quantizer uses (axis 0 = the J output capsules), so plan
            # and weights cannot disagree
            _, ns = qf.quantize_per_channel(params["W"], axis=0)
            pc_W = tuple(int(n) for n in ns)
            pc_shift = tuple(qf.out_shift(in_frac, f, f_uhat)
                             for f in pc_W)
        return RoutingPlan(
            **fields, softmax_impl=self.softmax_impl,
            squash_impl=self.squash_impl,
            W_frac_per_out=pc_W, uhat_shift_per_out=pc_shift)

    def quantize(self, params, plan: RoutingPlan) -> dict:
        if plan.per_out:
            # quantize with the PLAN's per-capsule formats (like the
            # conv's per-channel path) so plan edits stay consistent
            # with the shifts fwd_q7 will apply
            return {"W": qf.quantize_with_fracs(params["W"],
                                                plan.W_frac_per_out,
                                                axis=0)}
        return {"W": qf.quantize(params["W"], plan.W_frac)}

    def fwd_q7(self, qweights, plan: RoutingPlan, u, *, backend="jnp",
               rounding="floor"):
        be = get_backend(backend)
        shift = plan.uhat_shift_per_out if plan.per_out \
            else plan.uhat_shift
        with jax.named_scope("uhat"):
            u_hat = be.uhat_q7(qweights["W"], u, shift=shift,
                               rounding=rounding)
        with jax.named_scope("routing"):
            return be.routing_q7(u_hat, plan, rounding=rounding)

    @staticmethod
    def _softmax_fq(b, impl: str):
        """Couplings in Q0.7 the way the int8 graph computes them — the
        registered variant's fake-quant face (repro.nn.variants): the
        variant's forward approximation with the float softmax as the
        straight-through gradient surrogate.  Kept as a method so QAT
        code can probe one softmax face in isolation."""
        return REGISTRY.get("softmax", impl).fq(b)

    def fwd_fq(self, params, plan: RoutingPlan, u, *, rounding="floor"):
        """Fake-quant routing: u_hat, couplings, per-iteration s/v and
        the accumulated logits all snap to the grids routing_q7 uses
        (couplings and squash via the plan's variant references, like
        the backends; the logit clamp models add_q7's int8 saturation)."""
        if plan.per_out:
            W = qf.fake_quant_with_fracs(params["W"],
                                         plan.W_frac_per_out, axis=0)
        else:
            W = qf.fake_quant(params["W"], plan.W_frac)
        u_hat = qf.fake_quant(jnp.einsum("jiod,bid->bjio", W, u),
                              plan.uhat_frac, rounding)
        return _route_fq(u_hat, plan, rounding)


# ---------------------------------------------------------------------------
# the routing loop's faces, shared by the class and the local routing
# ---------------------------------------------------------------------------
def _routing_tap_names(name: str, routings: int) -> tuple:
    return ((f"{name}.u_hat",)
            + tuple(f"{name}.s/{r}" for r in range(routings))
            + tuple(f"{name}.logits/{r}" for r in range(routings - 1)))


def _route_f32(u_hat, routings: int, bias=None):
    """Float Alg. 5 over u_hat [B, J, I, O] (+ a [J, O] bias on every
    s) -> (v [B, J, O], taps)."""
    taps = {"u_hat": u_hat}
    b = jnp.zeros(u_hat.shape[:3], jnp.float32)
    v = None
    for r in range(routings):
        c = jax.nn.softmax(b, axis=1)
        s = jnp.einsum("bji,bjio->bjo", c, u_hat)
        if bias is not None:
            s = s + bias
        taps[f"s/{r}"] = s
        v = squash(s, axis=-1)
        if r < routings - 1:
            a = jnp.einsum("bjio,bjo->bji", u_hat, v)
            taps[f"agree/{r}"] = a
            b = b + a
            taps[f"logits/{r}"] = b
    return v, taps


def _routing_fields(name: str, routings: int, stats: TapStats, in_frac: int,
                    f_W: int) -> dict:
    """RoutingPlan's formats and shifts (Alg. 6) from the taps of
    `_route_f32`."""
    fb = qf.frac_bits
    f_uhat = fb(stats[f"{name}.u_hat"])
    # logit format is shared across iterations (b accumulates
    # agreements), capped at the Q0.7 barrier
    max_logit = max([stats.get(f"{name}.logits/{r}")
                     for r in range(routings - 1)] + [1e-6])
    f_logit = min(fb(max_logit), 7)
    f_s = tuple(fb(stats[f"{name}.s/{r}"]) for r in range(routings))
    return dict(
        uhat_shift=qf.out_shift(in_frac, f_W, f_uhat),
        logit_frac=f_logit,
        caps_out_shifts=tuple(qf.out_shift(f_uhat, 7, f) for f in f_s),
        caps_out_fracs=f_s,
        agree_shifts=tuple(qf.out_shift(f_uhat, 7, f_logit)
                           for _ in range(routings - 1)),
        in_frac=in_frac, W_frac=f_W, uhat_frac=f_uhat)


def _route_fq(u_hat, plan: RoutingPlan, rounding: str, bias=None):
    """Fake-quant Alg. 5 over an on-grid u_hat (+ an on-grid bias)."""
    sq = REGISTRY.get("squash", plan.squash_impl)
    b = jnp.zeros(u_hat.shape[:3], jnp.float32)
    v = None
    for r in range(plan.routings):
        c = CapsuleRouting._softmax_fq(b, plan.softmax_impl)
        s = jnp.einsum("bji,bjio->bjo", c, u_hat)
        if bias is not None:
            s = s + bias
        s = qf.fake_quant(s, plan.caps_out_fracs[r], rounding)
        v = sq.fq(s, plan.squash_out_frac, rounding)
        if r < plan.routings - 1:
            a = qf.fake_quant(jnp.einsum("bjio,bjo->bji", u_hat, v),
                              plan.logit_frac, rounding)
            b = qf.fake_quant(b + a, plan.logit_frac, rounding)
    return v


@dataclasses.dataclass(frozen=True)
class ConvCaps3D:
    """DeepCaps' 3D convolutional capsules: votes by one conv shared by
    all input capsule types, then dynamic routing at each position.

    Input a [B, H, W, I, D].  Votes: a 3x3 SAME conv over (y, x) of each
    type's [H, W, D] grid, D -> J * O channels with no bias, the same
    kernel W [k, k, D, J * O] for every type i (a Conv3D whose depth
    kernel and stride are D).  Routing: at each (b, y, x), `routings`
    iterations from I types to J, s_j = sum_i c_ij u_j|i + bias_j
    (SegCaps' update_routing), each group a row of the local routing.
    Output [B, H, W, J, O].  Taps: those of the routing loop."""
    name: str
    in_caps: int                    # I
    in_dim: int                     # D
    out_caps: int                   # J
    out_dim: int                    # O
    kernel: int = 3
    routings: int = 3
    softmax_impl: str = DEFAULT_SOFTMAX
    squash_impl: str = DEFAULT_SQUASH
    inputs: tuple = ()

    def init(self, key) -> dict:
        k, fan_in = self.kernel, self.kernel * self.kernel * self.in_dim
        return {"W": jax.random.normal(
            key, (k, k, self.in_dim, self.out_caps * self.out_dim),
            jnp.float32) * (1.0 / fan_in) ** 0.5,
            "b": jnp.full((self.out_caps, self.out_dim), 0.1, jnp.float32)}

    @staticmethod
    def _types(a):
        """[B, H, W, I, D] -> [B * I, H, W, D]: one image per type."""
        B, H, W, I, D = a.shape
        return a.transpose(0, 3, 1, 2, 4).reshape(B * I, H, W, D)

    def _groups(self, votes, B):
        """[B * I, H, W, J * O] -> [B * H * W, J, I, O]."""
        _, H, W, _ = votes.shape
        u = votes.reshape(B, self.in_caps, H, W, self.out_caps,
                          self.out_dim)
        return u.transpose(0, 2, 3, 4, 1, 5).reshape(
            B * H * W, self.out_caps, self.in_caps, self.out_dim)

    def _grid(self, v, a):
        return v.reshape(a.shape[:3] + (self.out_caps, self.out_dim))

    def fwd_f32(self, params, a):
        votes = _conv(self._types(a), params["W"], None, 1, "SAME")
        v, taps = _route_f32(self._groups(votes, a.shape[0]), self.routings,
                             params["b"])
        return self._grid(v, a), taps

    def plan_tap_names(self) -> tuple:
        return _routing_tap_names(self.name, self.routings)

    def plan(self, params, stats: TapStats, in_frac: int) -> LocalRoutingPlan:
        fields = _routing_fields(self.name, self.routings, stats, in_frac,
                                 _weight_frac(params["W"]))
        f_b = _weight_frac(params["b"])
        return LocalRoutingPlan(
            **fields, softmax_impl=self.softmax_impl,
            squash_impl=self.squash_impl, b_frac=f_b,
            bias_shift=qf.bias_shift(fields["uhat_frac"], 7, f_b))

    def quantize(self, params, plan: LocalRoutingPlan) -> dict:
        return {"W": qf.quantize(params["W"], plan.W_frac),
                "b": qf.quantize(params["b"], plan.b_frac)}

    def fwd_q7(self, qweights, plan: LocalRoutingPlan, a, *, backend="jnp",
               rounding="floor"):
        be = get_backend(backend)
        with jax.named_scope("votes"):
            votes = be.conv2d_q7(self._types(a), qweights["W"], None,
                                 plan.uhat_shift, 0, stride=1,
                                 rounding=rounding, padding="SAME")
            u_hat = self._groups(votes, a.shape[0])
        with jax.named_scope("routing"):
            b = qweights["b"].astype(jnp.int32)
            bias = jnp.left_shift(b, plan.bias_shift) \
                if plan.bias_shift >= 0 \
                else jnp.right_shift(b, -plan.bias_shift)
            v = be.local_routing_q7(u_hat, plan, rounding=rounding,
                                    bias=bias)
        return self._grid(v, a)

    def fwd_fq(self, params, plan: LocalRoutingPlan, a, *,
               rounding="floor"):
        W = qf.fake_quant(params["W"], plan.W_frac)
        bias = qf.fake_quant(params["b"], plan.b_frac)
        votes = qf.fake_quant(_conv(self._types(a), W, None, 1, "SAME"),
                              plan.uhat_frac, rounding)
        v = _route_fq(self._groups(votes, a.shape[0]), plan, rounding, bias)
        return self._grid(v, a)
