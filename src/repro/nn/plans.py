"""Typed quantization plans — the per-layer replacement for the old
string-keyed shift table.

A plan is everything a layer needs to execute its int8 path: Qm.n formats
for its weights and activations plus the power-of-two shifts between them
(paper Alg. 6).  Each layer derives its own plan from its calibration taps
(`layer.plan(params, stats, in_frac)`), and the pipeline threads the
activation format from one plan's `out_frac` into the next layer's
`in_frac` — the contract the old design encoded as ~25 magic dict keys.
"""
from __future__ import annotations

import dataclasses

from repro.nn import variants as _variants


@dataclasses.dataclass(frozen=True)
class TapStats:
    """max|x| observed on the calibration set, per tap name.

    Tap names are `<layer>.<tap>` (e.g. "conv0.out", "caps.s/1") plus the
    pipeline-level "input"."""
    max_abs: dict

    def __getitem__(self, name: str) -> float:
        return self.max_abs[name]

    def get(self, name: str, default: float = 0.0) -> float:
        return self.max_abs.get(name, default)


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """int8 conv: out_shift rescales the int32 accumulator into the
    output format; bias_shift aligns the bias into the accumulator.

    Per-channel mode (opt-in, beyond-paper but still shift-only): each
    output channel c gets its own weight format `w_frac_per_channel[c]`,
    so the accumulator scale — and therefore out/bias shift — varies per
    channel.  Empty tuples mean per-tensor (the paper's scheme); the
    scalar fields always hold the per-tensor derivation so compat
    translations keep working."""
    in_frac: int
    w_frac: int
    b_frac: int
    out_frac: int
    out_shift: int
    bias_shift: int
    w_frac_per_channel: tuple = ()
    out_shift_per_channel: tuple = ()
    bias_shift_per_channel: tuple = ()

    @property
    def per_channel(self) -> bool:
        return bool(self.w_frac_per_channel)


@dataclasses.dataclass(frozen=True)
class PrimaryCapsPlan:
    """conv plan + the integer squash that lands capsules in Q0.7.

    `squash_impl` is a validated reference into the operator-variant
    registry (`repro.nn.variants`): construction rejects unknown names,
    so a plan — whether built by `plan()`, read back from QAT's JSON
    side-car, or imported from a `.capsbin` — can only ever name a
    squash the backends, the EdgeVM, and the C emitter all implement."""
    conv: ConvPlan
    squash_out_frac: int = 7
    squash_impl: str = _variants.DEFAULT_SQUASH

    def __post_init__(self):
        _variants.REGISTRY.validate("squash", self.squash_impl)

    @property
    def out_frac(self) -> int:
        return self.squash_out_frac


@dataclasses.dataclass(frozen=True)
class RoutingPlan:
    """Dynamic routing (Alg. 5): one caps-output shift/format pair per
    iteration, one agreement shift per non-final iteration, a shared
    logit format, and the softmax operator variant as a plan field
    (previously a method monkey-patched onto QCapsNet)."""
    uhat_shift: int
    logit_frac: int
    caps_out_shifts: tuple
    caps_out_fracs: tuple
    agree_shifts: tuple              # derived for a Q0.7 squash output;
    #                                  backends add (out_frac - 7) when
    #                                  squash_out_frac is edited
    softmax_impl: str = _variants.DEFAULT_SOFTMAX   # registry reference
    in_frac: int = 7                # post-squash capsules are Q0.7
    W_frac: int = 0                 # bookkeeping for requantization/export
    uhat_frac: int = 0
    squash_out_frac: int = 7        # Q0.7 default; a plan edit, like softmax
    squash_impl: str = _variants.DEFAULT_SQUASH     # registry reference
    # per-output-capsule weight formats (opt-in, the routing analogue of
    # ConvPlan's per-channel tables): entry j is the Qm.n format of
    # W[j, ...] and the matching u_hat requantization shift.  Empty
    # tuples mean per-tensor (the paper's scheme).
    W_frac_per_out: tuple = ()
    uhat_shift_per_out: tuple = ()

    def __post_init__(self):
        _variants.REGISTRY.validate("softmax", self.softmax_impl)
        _variants.REGISTRY.validate("squash", self.squash_impl)

    @property
    def per_out(self) -> bool:
        return bool(self.W_frac_per_out)

    @property
    def routings(self) -> int:
        return len(self.caps_out_shifts)

    @property
    def out_frac(self) -> int:
        return self.squash_out_frac


@dataclasses.dataclass(frozen=True)
class LocalRoutingPlan(RoutingPlan):
    """Local routing with a bias (DeepCaps' 3D capsule layer): a
    RoutingPlan for the votes and the loop, plus the bias's format.
    The bias is added to every s in the accumulator's format
    (uhat_frac + 7), so it is left-shifted by `bias_shift`."""
    b_frac: int = 0
    bias_shift: int = 0


@dataclasses.dataclass(frozen=True)
class MergePlan:
    """A layer that merges the outputs of several layers into one int8
    tensor (a capsule add, a concat): each input `k` is shifted right
    by `shifts[k]` (left where negative) from its format `in_fracs[k]`
    into the common `out_frac`."""
    in_fracs: tuple
    out_frac: int
    shifts: tuple


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """The whole network's quantization decision: the input image format
    plus one typed plan per layer, keyed by layer name in walk order."""
    input_frac: int
    layers: dict

    def __getitem__(self, name: str):
        return self.layers[name]

    @property
    def variants(self) -> "_variants.VariantSet":
        """The operator-variant selection this plan carries (one softmax
        + one squash reference; see repro.nn.variants.VariantSet)."""
        return _variants.VariantSet.of_plan(self)

    def check(self) -> list:
        """Lint this plan's shift/frac algebra, per-channel tables,
        variant references and layer chaining (repro.analysis.plancheck)
        — returns the diagnostics, empty when clean."""
        from repro.analysis.plancheck import check_pipeline_plan
        return check_pipeline_plan(self)


_PLAN_KINDS = {}                      # class name -> plan dataclass


def _register(cls):
    _PLAN_KINDS[cls.__name__] = cls
    return cls


for _cls in (ConvPlan, PrimaryCapsPlan, RoutingPlan,
             LocalRoutingPlan, MergePlan):
    _register(_cls)


def plan_to_json(plan) -> dict:
    """Typed plan -> JSON-safe dict (used by captrain's QAT checkpoints
    and anything else that wants a plan outside a Python process)."""
    if isinstance(plan, PipelinePlan):
        return {"kind": "PipelinePlan", "input_frac": plan.input_frac,
                "layers": {k: plan_to_json(p)
                           for k, p in plan.layers.items()}}
    d = {"kind": type(plan).__name__}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if dataclasses.is_dataclass(v):
            v = plan_to_json(v)
        elif isinstance(v, tuple):
            v = list(v)
        d[f.name] = v
    return d


def plan_from_json(d: dict):
    """Inverse of plan_to_json; round-trips bit-exactly (all-int plans)."""
    kind = d["kind"]
    if kind == "PipelinePlan":
        return PipelinePlan(input_frac=d["input_frac"],
                            layers={k: plan_from_json(p)
                                    for k, p in d["layers"].items()})
    cls = _PLAN_KINDS[kind]
    kw = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue        # field added after this JSON was written:
            #                 fall back to the dataclass default
        v = d[f.name]
        if isinstance(v, dict) and "kind" in v:
            v = plan_from_json(v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[f.name] = v
    return cls(**kw)        # variant references re-validate in __post_init__


def plan_scalars(plan) -> int:
    """Number of scalar entries a plan materializes at runtime (the
    analogue of the old shift table's length, for footprint accounting)."""
    if isinstance(plan, PipelinePlan):
        return 1 + sum(plan_scalars(p) for p in plan.layers.values())
    n = 0
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, int):
            n += 1
        elif isinstance(v, tuple):
            n += len(v)
        elif dataclasses.is_dataclass(v):
            n += plan_scalars(v)
    return n
