"""Selectable int8 op backends for the quantized execution path.

`jnp`    — the pure-jnp oracle semantics from repro.quant.int8_ops: the
           bit-exact reference every other backend must reproduce.
           Operator variants (softmax/squash, see repro.nn.variants) are
           resolved through the variant registry, never by string
           comparison here.
`pallas` — the TPU kernels from repro.kernels: Pallas squash and the
           FUSED routing kernel (u_hat resident in VMEM, DESIGN §7).
           The fused kernels implement only the default ("q7" softmax,
           "exact" squash, Q0.7 output) plan; any other variant falls
           back to the oracle loop — bit-identically, but observably:
           every fallback decision increments `PallasBackend.fallbacks`
           and warns once per (op, variant) (no more silent degradation;
           the serving registry adds the per-model warning).

Both backends are bit-identical on every plan — the fused kernel is a
perf change, not a semantics change (tests/test_kernels.py).
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from repro.dist import api
from repro.nn.variants import REGISTRY
from repro.obs import METRICS, MetricsRegistry
from repro.quant import int8_ops as q


class JnpBackend:
    """Oracle backend: exact paper/CMSIS integer semantics in plain jnp."""

    name = "jnp"

    def conv2d_q7(self, x, w, b, out_shift, bias_shift, *, stride, rounding,
                  padding="VALID"):
        return q.conv2d_q7(x, w, b, out_shift, bias_shift,
                           stride=stride, padding=padding,
                           rounding=rounding)

    def conv2d_q7_per_channel(self, x, w, b, out_shifts, bias_shifts, *,
                              stride, rounding, padding="VALID"):
        """Per-output-channel requantization (ConvPlan.per_channel).  The
        conv itself is the same XLA int8 conv on every backend; only the
        shift step becomes a table lookup, so Pallas inherits this."""
        return q.conv2d_q7_per_channel(x, w, b, out_shifts, bias_shifts,
                                       stride=stride, padding=padding,
                                       rounding=rounding)

    def relu_q7(self, x):
        return q.relu_q7(x)

    def squash_q7(self, s, *, in_frac, out_frac=7, impl=None):
        impl = impl or REGISTRY.default("squash")
        return REGISTRY.get("squash", impl).q7(s, in_frac=in_frac,
                                               out_frac=out_frac)

    def softmax_q7(self, x, *, in_frac, impl=None):
        impl = impl or REGISTRY.default("softmax")
        return REGISTRY.get("softmax", impl).q7(x, in_frac)

    def uhat_q7(self, W, u, *, shift, rounding):
        """calc_inputs_hat: W int8 [J,I,O,D] x u int8 [B,I,D] -> int8
        u_hat [B,J,I,O] (int32 accumulation, one shift).  `shift` is
        either a scalar (per-tensor W format) or a length-J sequence
        (RoutingPlan.uhat_shift_per_out), applied per output capsule."""
        acc = jnp.einsum("jiod,bid->bjio", W.astype(jnp.int32),
                         u.astype(jnp.int32))
        if isinstance(shift, (tuple, list)):
            shifts = jnp.asarray(shift, jnp.int32)[None, :, None, None]
            return q.rshift_sat8_vec(acc, shifts, rounding)
        return q.rshift_sat8(acc, shift, rounding)

    def routing_q7(self, u_hat, plan, *, rounding, bias=None):
        """Alg. 5's r-iteration loop over an already-computed u_hat.
        `bias` (int32 [J, O], in the accumulator's format) is added to
        every s before its requantizing shift."""
        b = jnp.zeros(u_hat.shape[:3], jnp.int8)
        v = None
        for r in range(plan.routings):
            c = self.softmax_q7(b.swapaxes(1, 2), in_frac=plan.logit_frac,
                                impl=plan.softmax_impl).swapaxes(1, 2)
            acc = jnp.einsum("bji,bjio->bjo", c.astype(jnp.int32),
                             u_hat.astype(jnp.int32))
            if bias is not None:
                acc = acc + bias
            s_q = q.rshift_sat8(acc, plan.caps_out_shifts[r], rounding)
            v = self.squash_q7(s_q, in_frac=plan.caps_out_fracs[r],
                               out_frac=plan.out_frac,
                               impl=plan.squash_impl)
            if r < plan.routings - 1:
                acc = jnp.einsum("bjio,bjo->bji", u_hat.astype(jnp.int32),
                                 v.astype(jnp.int32))
                # agree_shifts were derived for a Q0.7 squash output
                # (layers.py); compensate when the plan's squash_out_frac
                # has been edited so logits keep their Q(f_logit) format
                a = q.rshift_sat8(
                    acc, plan.agree_shifts[r] + plan.out_frac - 7, rounding)
                b = q.add_q7(b, a)
        return v

    def local_routing_q7(self, u_hat, plan, *, rounding, bias=None):
        """Local routing: the same loop over u_hat [G, J, I, O], each of
        the G rows a local group (a batch row at one output position)."""
        return self.routing_q7(u_hat, plan, rounding=rounding, bias=bias)


# the fallback target for PallasBackend: a plain oracle instance, so a
# routing-level fallback runs the WHOLE loop on oracle ops and records
# exactly one counter entry per fallback decision (re-entering the
# pallas squash_q7 from inside the oracle loop would double-count)
_JNP_ORACLE = JnpBackend()


class PallasBackend(JnpBackend):
    """TPU-kernel backend (interpret mode on CPU): Pallas squash + the
    fused routing kernel.  Convs stay on the XLA int8 conv (the MXU path
    the paper's pcap maps to; there is no bespoke conv kernel)."""

    name = "pallas"

    def __init__(self, metrics: MetricsRegistry | None = None):
        # fallback DECISIONS (one per trace / direct call, not per
        # served image) are counted in a metrics registry, labeled
        # (op, variant).  A bare PallasBackend() gets a private registry
        # (fresh counters, the semantics the old ad-hoc Counter had);
        # the shared BACKENDS["pallas"] singleton records into the
        # process-default obs.METRICS so one snapshot sees it.
        self.metrics = MetricsRegistry("pallas") if metrics is None \
            else metrics
        self._fallback_counter = self.metrics.counter(
            "pallas.fallback_decisions",
            help="pallas->jnp-oracle fallback decisions by (op, variant)")
        self._warned: set = set()

    @property
    def fallbacks(self):
        """Counter-compatible view keyed by (op, variant) — the
        pre-registry attribute, preserved (tests/test_variants.py)."""
        return self._fallback_counter.view("op", "variant")

    def _fallback(self, op: str, variant: str):
        self._fallback_counter.inc(op=op, variant=variant)
        if (op, variant) not in self._warned:
            self._warned.add((op, variant))
            warnings.warn(
                f"pallas backend has no fused {op} kernel for variant "
                f"{variant!r}; falling back to the jnp oracle "
                "(bit-identical, slower)", RuntimeWarning, stacklevel=3)

    def squash_q7(self, s, *, in_frac, out_frac=7, impl=None):
        impl = impl or REGISTRY.default("squash")
        if impl != REGISTRY.default("squash"):
            self._fallback("squash", impl)
            return super().squash_q7(s, in_frac=in_frac, out_frac=out_frac,
                                     impl=impl)
        from repro.kernels import ops as kops
        return api.batch_local(
            lambda x: kops.squash_q7(x, in_frac=in_frac, out_frac=out_frac),
            s)

    def _fused_routing(self, op, u_hat, plan, *, rounding, bias, local):
        # the fused kernel implements only the default variants and the
        # Q0.7 squash output; other plans take the oracle loop
        for part, have, default in (
                ("softmax", plan.softmax_impl, REGISTRY.default("softmax")),
                ("squash", plan.squash_impl, REGISTRY.default("squash")),
                ("out_frac", f"Q0.{plan.out_frac}", "Q0.7")):
            if have != default:
                self._fallback(f"{op}.{part}", have)
                return _JNP_ORACLE.routing_q7(u_hat, plan,
                                              rounding=rounding, bias=bias)
        from repro.kernels import ops as kops
        return api.batch_local(
            lambda u: kops.routing_q7(
                u, num_iters=plan.routings,
                caps_out_shifts=plan.caps_out_shifts,
                caps_out_fracs=plan.caps_out_fracs,
                agree_shifts=plan.agree_shifts,
                logit_frac=plan.logit_frac, rounding=rounding, bias=bias,
                local=local),
            u_hat)

    def routing_q7(self, u_hat, plan, *, rounding, bias=None):
        return self._fused_routing("routing", u_hat, plan, rounding=rounding,
                                   bias=bias, local=False)

    def local_routing_q7(self, u_hat, plan, *, rounding, bias=None):
        """The grouped entry (`routing3d_q7_pallas`), one group a grid
        step; its fallbacks count under the op "routing3d"."""
        return self._fused_routing("routing3d", u_hat, plan,
                                   rounding=rounding, bias=bias, local=True)


BACKENDS = {"jnp": JnpBackend(), "pallas": PallasBackend(metrics=METRICS)}


def get_backend(backend):
    """Resolve a backend name (or pass an OpBackend-shaped object through)."""
    if isinstance(backend, str):
        try:
            return BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown backend {backend!r}; have {sorted(BACKENDS)}")
    return backend
