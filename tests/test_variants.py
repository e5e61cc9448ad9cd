"""Tests for the first-class operator-variant API (repro.nn.variants).

Pinned guarantees:
  * every registered (softmax, squash) combination executes bit-
    identically across `fwd_q7` and the NumPy `EdgeVM` on edge_tiny,
    for both rounding modes, and round-trips through the QAT plan JSON
    side-car codec and the `.capsbin` attrs (export -> `load_qnet` ->
    re-lower `same_as` -> VM bit-parity);
  * variant references are validated everywhere they enter: plan
    construction, plan JSON, imported artifacts, and the CLIs all
    reject unknown names with the registered ones listed;
  * variant selection is a pure plan edit (`with_variants`): weights,
    shifts, and non-variant layer plans are untouched (identity-
    preserved), and editing back restores the original bits;
  * the pallas backend's oracle fallback for non-default variants is
    observable — a counter per (op, variant) plus one warning per (op,
    variant) / per (model, variant) — never silent;
  * QAT's fake-quant faces follow the plan's variants: the approx
    softmax fq face reproduces `int8_ops.softmax_q7_approx` exactly on
    the integer grid;
  * acceptance: on the trained edge_tiny seed, every approximate
    variant's int8 accuracy is within 1.0 % of the q7+exact baseline
    (the ISLPED'22 claim this repo inherits), for both roundings.
"""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.edge import EdgeVM, lower, to_qnet
from repro.edge.program import EdgeProgram
from repro.nn.backend import PallasBackend
from repro.nn.pipeline import CapsPipeline
from repro.nn.plans import RoutingPlan, plan_from_json, plan_to_json
from repro.nn.variants import (REGISTRY, VariantSet, all_variant_sets)
from repro.quant import int8_ops as q
from repro.serving import EDGE_TINY, ModelRegistry, ModelSpec

ALL_SETS = all_variant_sets()
_cache = {}


def built(rounding="floor"):
    """edge_tiny PTQ build + int8 probe inputs, cached per rounding;
    variant sweeps are plan edits on top (weights shared by design)."""
    if rounding not in _cache:
        pipe = CapsPipeline.from_config(EDGE_TINY)
        params = pipe.init(jax.random.key(0))
        rng = np.random.default_rng(7)
        calib = jnp.asarray(rng.uniform(
            0, 1, (16,) + EDGE_TINY.input_shape).astype(np.float32))
        x = jnp.asarray(rng.uniform(
            0, 1, (2,) + EDGE_TINY.input_shape).astype(np.float32))
        qnet = pipe.quantize(params, calib, rounding=rounding)
        _cache[rounding] = (qnet, np.asarray(qnet.quantize_input(x)))
    return _cache[rounding]


# ---------------------------------------------------------------------------
# registry + VariantSet basics
# ---------------------------------------------------------------------------
def test_registry_defaults_and_names():
    assert REGISTRY.default("softmax") == "q7"
    assert REGISTRY.default("squash") == "exact"
    assert set(REGISTRY.names("softmax")) == {"q7", "precise", "approx"}
    assert set(REGISTRY.names("squash")) == {"exact", "approx"}
    v = REGISTRY.get("softmax", "approx")
    assert v.plan_field == "softmax_impl"
    assert v.c_symbol == "capsnet_softmax_q7_approx"


def test_unknown_variant_errors_list_registered_names():
    with pytest.raises(ValueError, match="approx, precise, q7"):
        REGISTRY.get("softmax", "nope")
    with pytest.raises(ValueError, match="approx, exact"):
        VariantSet(squash="nope")
    # plan dataclasses validate at construction too (frozen replace
    # included), so no unvalidated reference can enter a plan
    rp = built()[0].plan["caps"]
    with pytest.raises(ValueError, match="registered"):
        dataclasses.replace(rp, softmax_impl="evil")


def test_variant_set_attaches_to_plan():
    qnet, x_q = built()
    assert qnet.plan.variants == VariantSet()
    assert qnet.variants.is_default()

    vs = VariantSet(softmax="approx", squash="approx")
    q2 = qnet.with_variants(vs)
    assert q2.plan.variants == vs and q2.variants.tag == "approx+approx"
    # pure plan edit: weights untouched, conv plans identity-preserved
    assert q2.qweights is qnet.qweights
    for name, p in q2.plan.layers.items():
        if not (hasattr(p, "softmax_impl") or hasattr(p, "squash_impl")):
            assert p is qnet.plan.layers[name]
    # editing back restores the original bits
    np.testing.assert_array_equal(
        np.asarray(q2.with_variants(VariantSet()).forward(
            jnp.asarray(x_q))),
        np.asarray(qnet.forward(jnp.asarray(x_q))))
    # and a with_squash edit equals building the pipeline that way
    pipe2 = CapsPipeline.from_config(EDGE_TINY, squash_impl="approx")
    qnet2 = pipe2.quantize(
        CapsPipeline.from_config(EDGE_TINY).init(jax.random.key(0)),
        jnp.asarray(np.random.default_rng(7).uniform(
            0, 1, (16,) + EDGE_TINY.input_shape).astype(np.float32)))
    np.testing.assert_array_equal(
        np.asarray(qnet.with_squash("approx").forward(jnp.asarray(x_q))),
        np.asarray(qnet2.forward(jnp.asarray(x_q))))


def test_from_config_rejects_conflicting_variant_args():
    with pytest.raises(ValueError, match="not both"):
        CapsPipeline.from_config(EDGE_TINY, softmax_impl="q7",
                                 variants=VariantSet())


# ---------------------------------------------------------------------------
# acceptance: bit-parity + serialization for EVERY registered variant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rounding", ["floor", "nearest"])
@pytest.mark.parametrize("vs", ALL_SETS, ids=lambda v: v.tag)
def test_every_variant_bit_identical_host_vs_vm(vs, rounding):
    qnet, x_q = built(rounding)
    qv = qnet.with_variants(vs)
    program = lower(qv)
    routing = next(op for op in program.ops
                   if op.kind == "CAPS_ROUTING_Q7")
    assert routing.attrs["softmax_impl"] == vs.softmax
    assert routing.attrs["squash_impl"] == vs.squash
    np.testing.assert_array_equal(
        EdgeVM(program).run(x_q),
        np.asarray(qv.forward(jnp.asarray(x_q))))


@pytest.mark.parametrize("vs", ALL_SETS, ids=lambda v: v.tag)
def test_every_variant_round_trips_json_and_capsbin(vs, tmp_path):
    qnet, x_q = built()
    qv = qnet.with_variants(vs)

    # QAT plan JSON side-car codec
    restored = plan_from_json(json.loads(
        json.dumps(plan_to_json(qv.plan), sort_keys=True)))
    assert restored == qv.plan and restored.variants == vs

    # .capsbin attrs: export -> load_qnet -> re-lower -> VM bit-parity
    program = lower(qv)
    paths = program.save(tmp_path / "m")
    reloaded = EdgeProgram.load(paths["capsbin"])
    assert program.same_as(reloaded)
    q2 = to_qnet(reloaded)
    assert q2.variants == vs
    assert lower(q2, name=program.name).same_as(program)
    np.testing.assert_array_equal(
        np.asarray(q2.forward(jnp.asarray(x_q))),
        EdgeVM(reloaded).run(x_q))


def test_pre_variant_artifact_defaults_everywhere(tmp_path):
    """A schedule with NO variant attrs (pre-variant artifact) defaults
    to q7+exact in every consumer — importer, VM, and C emitter — via
    the one shared registry accessor."""
    from repro.edge import emit_c

    qnet, x_q = built()
    program = lower(qnet)
    ops = tuple(dataclasses.replace(
        op, attrs={k: v for k, v in op.attrs.items()
                   if k not in ("softmax_impl", "squash_impl")})
        for op in program.ops)
    old = dataclasses.replace(program, ops=ops)
    q2 = to_qnet(old)
    assert q2.variants == VariantSet()
    np.testing.assert_array_equal(EdgeVM(old).run(x_q),
                                  np.asarray(qnet.forward(jnp.asarray(x_q))))
    assert "approx" not in emit_c(old)["c"]


def test_register_evicts_cached_model_and_executables():
    """Re-registering a spec (the CLI --softmax/--squash path) must not
    keep serving the previously built model from the cache."""
    spec = ModelSpec("t@jnp", EDGE_TINY, dataset="uniform", calib_n=4)
    reg = ModelRegistry(specs={spec.model_id: spec})
    assert reg.model("t@jnp").variants.is_default()
    reg.executable("t@jnp", 1)
    reg.register(dataclasses.replace(spec, softmax_impl="approx"))
    assert reg.model("t@jnp").variants.softmax == "approx"
    assert reg.quantize_count == 2
    exe = reg.executable("t@jnp", 1)      # recompiled, not the stale wave
    assert reg.compile_count == 2 and exe is not None


def test_tampered_unknown_variant_is_rejected(tmp_path):
    qnet, x_q = built()
    # plan JSON side-car tampered with an unregistered softmax
    d = plan_to_json(qnet.plan)
    d["layers"]["caps"]["softmax_impl"] = "evil"
    with pytest.raises(ValueError, match="approx, precise, q7"):
        plan_from_json(d)
    # .capsbin whose routing op names an unregistered variant: the file
    # parses (attrs are opaque bytes) but neither the importer nor the
    # VM will execute it
    program = lower(qnet)
    ops = tuple(dataclasses.replace(
        op, attrs={**op.attrs, "softmax_impl": "evil"})
        if op.kind == "CAPS_ROUTING_Q7" else op for op in program.ops)
    bad = dataclasses.replace(program, ops=ops)
    paths = bad.save(tmp_path / "bad")
    loaded = EdgeProgram.load(paths["capsbin"])
    with pytest.raises(ValueError, match="registered"):
        to_qnet(loaded)
    with pytest.raises(ValueError, match="registered"):
        EdgeVM(loaded).run(x_q)


# ---------------------------------------------------------------------------
# pallas fallback observability (no more silent degradation)
# ---------------------------------------------------------------------------
def test_pallas_fallback_counter_and_warn_once():
    qnet, x_q = built()
    qv = qnet.with_variants(VariantSet(softmax="approx", squash="approx"))
    be = PallasBackend()                 # fresh counters, not the shared one
    assert not be.fallbacks

    def run():
        return np.asarray(qv.pipeline.forward_q7(
            qv.qweights, qv.plan, jnp.asarray(x_q), backend=be,
            rounding=qv.rounding))

    with pytest.warns(RuntimeWarning, match="falling back"):
        v_pal = run()
    # bit-identical to the oracle, but counted: exactly ONE decision per
    # fallback site per forward (pcap squash + routing entry; the oracle
    # loop the routing falls back to must not re-count its inner squash)
    assert be.fallbacks[("squash", "approx")] == 1
    assert be.fallbacks[("routing.softmax", "approx")] == 1
    assert ("routing.squash", "approx") not in be.fallbacks
    np.testing.assert_array_equal(
        v_pal, np.asarray(qv.forward(jnp.asarray(x_q))))
    before = dict(be.fallbacks)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # a second warning would raise
        run()
    assert be.fallbacks[("squash", "approx")] > before[("squash", "approx")]


def test_pallas_out_frac_fallback_counted():
    """The fused routing kernel emits Q0.7 only: a plan whose squash
    output is edited to Q0.6 takes the oracle loop, counted and warned
    like the variant fallbacks, and stays bit-identical to the oracle."""
    qnet, x_q = built()
    caps = dataclasses.replace(qnet.plan.layers["caps"], squash_out_frac=6)
    q6 = dataclasses.replace(qnet, plan=dataclasses.replace(
        qnet.plan, layers={**qnet.plan.layers, "caps": caps}))
    be = PallasBackend()
    with pytest.warns(RuntimeWarning, match="falling back"):
        v_pal = np.asarray(q6.pipeline.forward_q7(
            q6.qweights, q6.plan, jnp.asarray(x_q), backend=be,
            rounding=q6.rounding))
    assert dict(be.fallbacks) == {("routing.out_frac", "Q0.6"): 1}
    assert be.metrics.counter("pallas.fallback_decisions").total() == 1
    np.testing.assert_array_equal(
        v_pal, np.asarray(q6.forward(jnp.asarray(x_q))))


def test_registry_warns_once_per_model_and_variant():
    spec = ModelSpec("tiny@pallas", EDGE_TINY, backend="pallas",
                     dataset="uniform", calib_n=4,
                     softmax_impl="approx", squash_impl="approx")
    reg = ModelRegistry(specs={spec.model_id: spec})
    with pytest.warns(RuntimeWarning, match="tiny@pallas"):
        reg.model("tiny@pallas")
    assert reg.variant_fallbacks == {"tiny@pallas": "approx+approx"}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        reg.install("tiny@pallas", reg.model("tiny@pallas"))  # same pair
    # the jnp backend never records a fallback
    jreg = ModelRegistry(specs={"t@jnp": dataclasses.replace(
        spec, model_id="t@jnp", backend="jnp")})
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        jreg.model("t@jnp")
    assert jreg.variant_fallbacks == {}
    # re-registering back to defaults clears the stale fallback report
    reg.register(dataclasses.replace(spec, softmax_impl="q7",
                                     squash_impl="exact"))
    assert reg.variant_fallbacks == {}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        reg.model("tiny@pallas")             # rebuilt on defaults: silent
    assert reg.variant_fallbacks == {}


# ---------------------------------------------------------------------------
# QAT faces follow the plan's variants
# ---------------------------------------------------------------------------
def test_fq_softmax_approx_matches_integer_op():
    """The approx softmax fake-quant face lands exactly on the codes
    `int8_ops.softmax_q7_approx` produces (both are powers of two with
    a power-of-two normalizer, so the match is bit-exact)."""
    from repro.nn.layers import CapsuleRouting

    rng = np.random.default_rng(5)
    f = 5
    b_q = rng.integers(-128, 128, (2, 7, 9)).astype(np.int8)
    b = jnp.asarray(b_q, jnp.float32) * 2.0 ** -f    # on the Q(f) grid

    c_fq = np.asarray(CapsuleRouting._softmax_fq(b, "approx"))  # axis 1
    c_int = np.asarray(q.softmax_q7_approx(
        jnp.asarray(b_q).swapaxes(1, 2), in_frac=f)).swapaxes(1, 2)
    np.testing.assert_array_equal(c_fq * 128.0, c_int.astype(np.float32))

    # adversarial normalizer: 16 max-tied logits + one tail at the -20
    # exponent clamp put the integer sum at 2^24 + 1 — a float32 sum
    # rounds that back to 2^24 and doubles every coupling; the fq face
    # must match the integer op here too (it mirrors the int32 sum, not
    # a float sum)
    f_adv = 1
    b_adv = np.zeros((1, 17, 1), np.int8)
    b_adv[0, 16, 0] = -128                   # -128 >> 1 = -64 -> clamp -20
    c_fq = np.asarray(CapsuleRouting._softmax_fq(
        jnp.asarray(b_adv, jnp.float32) * 2.0 ** -f_adv, "approx"))
    c_int = np.asarray(q.softmax_q7_approx(
        jnp.asarray(b_adv).swapaxes(1, 2), in_frac=f_adv)).swapaxes(1, 2)
    np.testing.assert_array_equal(c_fq * 128.0, c_int.astype(np.float32))


def test_fwd_fq_follows_squash_variant():
    """forward_fq trains against the plan's squash variant: flipping it
    changes the QAT forward, and its gradient still flows (STE)."""
    qnet, _ = built()
    pipe = CapsPipeline.from_config(EDGE_TINY)
    params = pipe.init(jax.random.key(1))
    x = jnp.asarray(np.random.default_rng(3).uniform(
        0, 1, (2,) + EDGE_TINY.input_shape).astype(np.float32))
    plan_exact = qnet.plan
    plan_apx = VariantSet(squash="approx").apply(plan_exact)
    v_exact = pipe.forward_fq(params, x, plan_exact)
    v_apx = pipe.forward_fq(params, x, plan_apx)
    assert not np.array_equal(np.asarray(v_exact), np.asarray(v_apx))
    g = jax.grad(lambda p: jnp.sum(
        pipe.forward_fq(p, x, plan_apx) ** 2))(params)
    assert float(jnp.max(jnp.abs(g["caps"]["W"]))) > 0.0


def test_trainer_carries_variants_into_qat_plan():
    from repro.captrain import CapsTrainer, TrainConfig

    tcfg = TrainConfig(dataset="edge_tiny", batch=8, microbatches=2,
                       calib_n=8, softmax_impl="approx",
                       squash_impl="approx")
    trainer = CapsTrainer(EDGE_TINY, tcfg)
    state = trainer.init_state()
    plan = trainer.derive_plan(state)
    assert plan.variants.tag == "approx+approx"
    qnet = trainer.quantize(state)
    assert qnet.variants.tag == "approx+approx"
    # and the quantized model still matches the VM bit for bit
    x_q = qnet.quantize_input(jnp.asarray(np.random.default_rng(0).uniform(
        0, 1, (2,) + EDGE_TINY.input_shape).astype(np.float32)))
    np.testing.assert_array_equal(
        EdgeVM(lower(qnet)).run(np.asarray(x_q)),
        np.asarray(qnet.forward(x_q)))


# ---------------------------------------------------------------------------
# CLIs
# ---------------------------------------------------------------------------
def test_export_caps_cli_exports_variants(tmp_path):
    from repro.launch import export_caps

    rc = export_caps.main(["--model", "edge_tiny", "--out", str(tmp_path),
                           "--softmax", "approx", "--squash", "approx",
                           "--verify-n", "2"])
    assert rc == 0
    manifest = json.loads(
        (tmp_path / "edge_tiny_jnp.manifest.json").read_text())
    routing = [o for o in manifest["ops"]
               if o["kind"] == "CAPS_ROUTING_Q7"][0]
    assert routing["attrs"]["softmax_impl"] == "approx"
    assert routing["attrs"]["squash_impl"] == "approx"
    c_src = (tmp_path / "edge_tiny_jnp.c").read_text()
    assert "capsnet_dynamic_routing_q7_softmax_approx_squash_approx(" \
        in c_src
    assert "capsnet_squash_q7_approx(" in c_src


def test_cli_unknown_variant_lists_choices(capsys):
    from repro.launch import export_caps, serve_caps

    for main in (export_caps.main, serve_caps.main):
        with pytest.raises(SystemExit) as e:
            main(["--softmax", "bogus"])
        assert e.value.code == 2
        err = capsys.readouterr().err
        for name in REGISTRY.names("softmax"):
            assert name in err


# ---------------------------------------------------------------------------
# accuracy acceptance (ISLPED'22 claim on the edge_tiny seed)
# ---------------------------------------------------------------------------
def test_approx_variants_within_one_percent_of_q7_baseline():
    """Trained edge_tiny seed: every approximate variant's int8 accuracy
    stays within 1.0 % (absolute) of the q7+exact baseline, for both
    roundings — and the Table-2 harness reports the tagged rows."""
    from repro.captrain import CapsTrainer, TrainConfig, eval_q7
    from repro.data.synthetic import make_image_dataset

    tcfg = TrainConfig(dataset="edge_tiny", batch=32, microbatches=4,
                       calib_n=32, lr=3e-3)
    trainer = CapsTrainer(EDGE_TINY, tcfg)
    state = trainer.init_state()
    state, _, _ = trainer.fit(state, 150)    # ~97 % converged seed
    images, labels = make_image_dataset("edge_tiny", 256, seed=999_999)

    for rounding in ("floor", "nearest"):
        qnet = trainer.quantize(state, rounding=rounding)
        acc_base = eval_q7(qnet, images, labels)
        for vs in ALL_SETS:
            if "approx" not in (vs.softmax, vs.squash):
                continue
            acc = eval_q7(qnet.with_variants(vs), images, labels)
            assert abs(acc - acc_base) <= 0.010 + 1e-9, \
                (rounding, vs.tag, acc, acc_base)


def test_table2_rows_report_variant_tag():
    from repro.captrain import TrainConfig, table2_rows
    from repro.captrain.evalq import format_rows

    tcfg = TrainConfig(dataset="edge_tiny", batch=16, microbatches=2,
                       calib_n=16)
    rows = table2_rows(EDGE_TINY, tcfg, float_steps=4, qat_steps=2,
                       roundings=("floor",), eval_n=32,
                       variants=VariantSet(softmax="approx",
                                           squash="approx"))
    assert [r.variant for r in rows] == ["approx+approx"]
    assert "approx+approx" in format_rows(rows)
