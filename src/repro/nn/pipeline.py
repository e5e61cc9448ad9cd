"""CapsPipeline: one typed graph walk for every execution face.

  forward    — float inference (optionally returning calibration taps)
  calibrate  — max|x| per tap over a reference dataset (Alg. 6 line 8)
  plan       — per-layer Qm.n plans, each layer's `in_frac` the format
               of the layer it reads
  quantize   — per-layer plans + int8 weights -> a QuantCapsNet
  forward_fq — fake-quantized float forward (QAT)
  forward_q7 — int8 inference on a selectable op backend

The layers are in execution order.  Each reads the output of the layer
before it, or of the layers its `inputs` name ("input" is the image), so
skips and concatenations (DeepCaps) walk the same way as the template's
chain (`_walk`).

The pipeline owns nothing numeric: every operation, tap, format and shift
belongs to a layer.  Adding a layer kind (deeper stacks, approximate-op
variants, per-channel PTQ) means writing one class against the CapsLayer
protocol — no cross-file string threading.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn.config import CapsNetConfig, DeepCapsConfig
from repro.nn.layers import (CapsAdd, CapsConcat, CapsuleRouting, ConvCaps3D,
                             PrimaryCaps, QuantConv2D)
from repro.nn.plans import PipelinePlan, TapStats, plan_scalars
from repro.nn.variants import VariantSet
from repro.obs import numerics as _health
from repro.quant import qformat as qf


def _walk(layers, h, apply):
    """Run `apply(layer, inp)` over the layers in order and return the
    last one's result.  `inp` is the result of the layer's named input,
    a tuple of them for several, or the previous layer's result."""
    outs, last = {"input": h}, "input"
    for l in layers:
        srcs = l.inputs or (last,)
        outs[l.name] = apply(l, outs[srcs[0]] if len(srcs) == 1
                             else tuple(outs[s] for s in srcs))
        last = l.name
    return outs[last]


@dataclasses.dataclass(frozen=True)
class CapsPipeline:
    cfg: CapsNetConfig | DeepCapsConfig
    layers: tuple

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, cfg: CapsNetConfig, softmax_impl: str | None = None,
                    per_channel: bool = False,
                    squash_impl: str | None = None,
                    variants: VariantSet | None = None,
                    per_channel_w: bool = False) -> "CapsPipeline":
        """Build the typed pipeline for a geometry config.

        Operator variants come from the registry (repro.nn.variants):
        pass a whole `variants=VariantSet(...)`, or the individual
        `softmax_impl=` / `squash_impl=` names (unknown names raise with
        the registered ones listed).  Omitted -> registry defaults.
        `per_channel` opts the convs into per-output-channel weight
        formats; `per_channel_w` does the same for the routing W
        (per-output-capsule formats, RoutingPlan.W_frac_per_out)."""
        if variants is None:
            variants = VariantSet(
                **{k: v for k, v in (("softmax", softmax_impl),
                                     ("squash", squash_impl))
                   if v is not None})
        elif softmax_impl is not None or squash_impl is not None:
            raise ValueError(
                "pass either variants= or softmax_impl=/squash_impl=, "
                "not both")
        layers = []
        cin = cfg.input_shape[2]
        for i, (f, k, s) in enumerate(zip(cfg.conv_filters, cfg.conv_kernels,
                                          cfg.conv_strides)):
            layers.append(QuantConv2D(f"conv{i}", k, s, cin, f, relu=True,
                                      per_channel=per_channel))
            cin = f
        layers.append(PrimaryCaps("pcap", cfg.pcap_kernel, cfg.pcap_stride,
                                  cin, cfg.pcap_caps, cfg.pcap_dim,
                                  per_channel=per_channel,
                                  squash_impl=variants.squash))
        layers.append(CapsuleRouting(
            "caps", cfg.num_classes, cfg.num_input_caps, cfg.caps_dim,
            cfg.pcap_dim, cfg.routings, softmax_impl=variants.softmax,
            squash_impl=variants.squash, per_channel=per_channel_w))
        return cls(cfg=cfg, layers=tuple(layers))

    @classmethod
    def from_deepcaps(cls, cfg: DeepCapsConfig) -> "CapsPipeline":
        """DeepCaps (arXiv:1904.09546): a SAME conv stem, capsule cells
        `a = CC(h, 2); out = CC(CC(a, 1), 1) + skip(a)` with skip = CC(a,
        1) in cells 1-3 and ConvCaps3D in the last, then class capsules
        routed from the last two cells' capsules concatenated."""
        def cc(name, inp, stride, in_ch, caps, dim):
            return PrimaryCaps(name, 3, stride, in_ch, caps, dim,
                               padding="SAME", grid=True, inputs=(inp,))

        layers = [QuantConv2D("stem", 3, 1, cfg.input_shape[2],
                              cfg.stem_filters, relu=True, padding="SAME")]
        src, cin = "stem", cfg.stem_filters
        for n, (caps, dim) in enumerate(cfg.cells, start=1):
            c, width = f"cell{n}", caps * dim
            skip = ConvCaps3D(f"{c}/skip3d", caps, dim, caps, dim,
                              routings=cfg.routings, inputs=(f"{c}/a",)) \
                if n == len(cfg.cells) \
                else cc(f"{c}/skip", f"{c}/a", 1, width, caps, dim)
            layers += [cc(f"{c}/a", src, 2, cin, caps, dim),
                       cc(f"{c}/b", f"{c}/a", 1, width, caps, dim),
                       cc(f"{c}/c", f"{c}/b", 1, width, caps, dim), skip,
                       CapsAdd(c, (f"{c}/c", skip.name))]
            src, cin = c, width
        n = len(cfg.cells)
        layers.append(CapsConcat("concat", (f"cell{n}", f"cell{n - 1}")))
        layers.append(CapsuleRouting("caps", cfg.num_classes,
                                     cfg.num_input_caps, cfg.caps_dim,
                                     cfg.cells[-1][1], cfg.routings))
        return cls(cfg=cfg, layers=tuple(layers))

    def layer(self, name: str):
        for l in self.layers:
            if l.name == name:
                return l
        raise KeyError(name)

    def init(self, key) -> dict:
        ks = jax.random.split(key, len(self.layers))
        return {l.name: l.init(k) for l, k in zip(self.layers, ks)}

    @staticmethod
    def param_bytes(params) -> int:
        """fp32 footprint of a param pytree (Table 2's numerator)."""
        return sum(4 * l.size for l in jax.tree_util.tree_leaves(params))

    # ------------------------------------------------------------------
    # float face
    # ------------------------------------------------------------------
    def forward(self, params, x, *, with_taps: bool = False):
        """x [B,H,W,C] float in [0,1] -> class capsules [B, J, O]."""
        taps = {"input": x}

        def step(l, h):
            h, t = l.fwd_f32(params.get(l.name, {}), h)
            for k, v in t.items():
                taps[f"{l.name}.{k}"] = v
            return h
        h = _walk(self.layers, x, step)
        return (h, taps) if with_taps else h

    def tap_names(self) -> tuple:
        """Every stats key any layer's plan() will read."""
        names = ["input"]
        for l in self.layers:
            names.extend(l.plan_tap_names())
        return tuple(names)

    # ------------------------------------------------------------------
    # calibration face (Alg. 6 line 8)
    # ------------------------------------------------------------------
    def calibrate(self, params, calib_images, batch: int = 64) -> TapStats:
        """Running max|x| per tap accumulates on device; the host sees one
        sync at the end, not one `float()` per tap per batch."""
        @jax.jit
        def batch_maxes(x):
            _, taps = self.forward(params, x, with_taps=True)
            return {k: jnp.max(jnp.abs(t)) for k, t in taps.items()}

        running = None
        n = calib_images.shape[0]
        for i in range(0, n, batch):
            m = batch_maxes(calib_images[i:i + batch])
            running = m if running is None else \
                jax.tree.map(jnp.maximum, running, m)
        if running is None:
            raise ValueError("empty calibration set")
        return TapStats({k: float(v)
                         for k, v in jax.device_get(running).items()})

    # ------------------------------------------------------------------
    # planning + quantization face (Alg. 6 & 7)
    # ------------------------------------------------------------------
    def plan(self, params, stats: TapStats) -> PipelinePlan:
        """Each layer derives its own plan; a layer's `in_frac` is the
        `out_frac` of the layer it reads (a tuple for several)."""
        input_frac = qf.frac_bits(stats["input"])
        plans: dict = {}

        def step(l, f_in):
            plans[l.name] = l.plan(params.get(l.name, {}), stats, f_in)
            return plans[l.name].out_frac
        _walk(self.layers, input_frac, step)
        return PipelinePlan(input_frac=input_frac, layers=plans)

    def quantize(self, params, calib_images, *, rounding: str = "floor",
                 backend: str = "jnp", batch: int = 64) -> "QuantCapsNet":
        from repro import obs
        with obs.span("ptq.calibrate", config=self.cfg.name):
            stats = self.calibrate(params, calib_images, batch=batch)
        with obs.span("ptq.plan", config=self.cfg.name):
            plan = self.plan(params, stats)
        with obs.span("ptq.quantize_weights", config=self.cfg.name):
            qweights = {l.name: l.quantize(params.get(l.name, {}),
                                           plan[l.name])
                        for l in self.layers}
        return QuantCapsNet(pipeline=self, plan=plan, qweights=qweights,
                            rounding=rounding, backend=backend)

    # ------------------------------------------------------------------
    # fake-quant face (QAT; see repro.captrain)
    # ------------------------------------------------------------------
    def forward_fq(self, params, x, plan: PipelinePlan, *,
                   rounding: str = "floor"):
        """Float forward with every int8 quantization point fake-applied
        on the plan's Qm.n grids (straight-through gradients).  The plan
        comes from the SAME `plan()` machinery PTQ uses, so a QAT model
        quantizes/lowers/serves with zero new conversion code."""
        index = {l.name: i for i, l in enumerate(self.layers)}

        def step(l, h):
            if _health._PROBE is None:             # hot path untouched
                return l.fwd_fq(params.get(l.name, {}), plan[l.name], h,
                                rounding=rounding)
            with _health.scope(l.name, index=index[l.name],
                               kind=type(l).__name__):
                return l.fwd_fq(params.get(l.name, {}), plan[l.name], h,
                                rounding=rounding)
        if _health._PROBE is None:
            return _walk(self.layers, qf.fake_quant(x, plan.input_frac),
                         step)
        with _health.scope("input"):
            h = qf.fake_quant(x, plan.input_frac)
        return _walk(self.layers, h, step)

    # ------------------------------------------------------------------
    # int8 face
    # ------------------------------------------------------------------
    def forward_q7(self, qweights, plan: PipelinePlan, x_q, *,
                   backend: str = "jnp", rounding: str = "floor"):
        """x_q int8 image in the plan's input format -> v int8 [B,J,O]."""
        index = {l.name: i for i, l in enumerate(self.layers)}

        def step(l, h):
            with jax.named_scope(l.name):          # trace metadata only
                if _health._PROBE is None:         # hot path untouched
                    return l.fwd_q7(qweights[l.name], plan[l.name], h,
                                    backend=backend, rounding=rounding)
                with _health.scope(l.name, index=index[l.name],
                                   kind=type(l).__name__):
                    h = l.fwd_q7(qweights[l.name], plan[l.name], h,
                                 backend=backend, rounding=rounding)
                    if not _health._is_tracer(h):
                        _health._PROBE.observe_output(
                            h, frac=plan[l.name].out_frac)
                    return h
        return _walk(self.layers, x_q, step)

    def quantize_input(self, x, plan: PipelinePlan):
        return qf.quantize(x, plan.input_frac)


@dataclasses.dataclass(frozen=True)
class QuantCapsNet:
    """A quantized CapsNet as a typed object: pipeline + plan + int8
    weights (the replacement for QCapsNet's string-keyed shift table)."""
    pipeline: CapsPipeline
    plan: PipelinePlan
    qweights: dict
    rounding: str = "floor"
    backend: str = "jnp"

    def quantize_input(self, x):
        return self.pipeline.quantize_input(x, self.plan)

    def forward(self, x_q):
        return self.pipeline.forward_q7(self.qweights, self.plan, x_q,
                                        backend=self.backend,
                                        rounding=self.rounding)

    def class_lengths(self, v_q):
        """||v|| per class, dequantized with the final layer's output
        format (not a hardcoded Q0.7 /128 — squash_out_frac is a plan
        field and non-default plans must score correctly)."""
        out_frac = self.plan[self.pipeline.layers[-1].name].out_frac
        v32 = v_q.astype(jnp.int32)
        return jnp.sqrt(jnp.sum(v32 * v32, axis=-1)
                        .astype(jnp.float32)) * (2.0 ** -out_frac)

    def memory_bytes(self) -> int:
        n = sum(l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(self.qweights))
        n += 4 * plan_scalars(self.plan)       # int32 shift/format table
        return int(n)

    def with_backend(self, backend: str) -> "QuantCapsNet":
        return dataclasses.replace(self, backend=backend)

    @property
    def variants(self) -> VariantSet:
        """The operator-variant selection the plan carries."""
        return self.plan.variants

    def with_variants(self, variants: VariantSet) -> "QuantCapsNet":
        """Return a model running `variants` — a pure plan edit (weights
        and shifts untouched; variant choices never affect Alg. 7's
        weight quantization), applied to every variant-bearing layer
        plan in the pipeline (deeper stacks may have several)."""
        return dataclasses.replace(self, plan=variants.apply(self.plan))

    def with_softmax(self, impl: str) -> "QuantCapsNet":
        """Softmax-only plan edit (see with_variants)."""
        return self.with_variants(
            dataclasses.replace(self.variants, softmax=impl))

    def with_squash(self, impl: str) -> "QuantCapsNet":
        """Squash-only plan edit (see with_variants)."""
        return self.with_variants(
            dataclasses.replace(self.variants, squash=impl))
