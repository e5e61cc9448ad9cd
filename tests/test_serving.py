"""Serving-subsystem tests (repro.serving) + the PR's satellite fixes.

Pinned guarantees:
  * engine waves are bit-identical to direct QuantCapsNet.forward —
    bucket padding cannot perturb real rows;
  * the scheduler is deterministic: same submissions -> same waves,
    buckets and bits;
  * the registry quantizes lazily (once) and reuses compiled wave
    executables per (model, bucket);
  * the sharded wave path matches the unsharded one bit-for-bit on a
    1-device mesh (and on a real 8-device mesh, slow tier);
  * with_softmax is a pure plan edit; class_lengths dequantizes with the
    plan's out_frac; calibrate's device-side accumulation matches the
    per-batch host-sync semantics it replaced.

Everything runs on the CIFAR-10 geometry (the paper's smallest) with one
module-scoped PTQ build.
"""
import dataclasses
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.nn import CIFAR10, CapsPipeline
from repro.nn.plans import ConvPlan, RoutingPlan
from repro.serving import (CapsServeEngine, ModelRegistry, ModelSpec,
                           ServeMetrics, compile_wave)

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


class FakeClock:
    """Monotone fake clock: every read advances 1s."""
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def served():
    cfg = CIFAR10
    pipe = CapsPipeline.from_config(cfg)
    params = pipe.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    calib = jnp.asarray(
        rng.uniform(0, 1, (16,) + cfg.input_shape).astype(np.float32))
    qnet = pipe.quantize(params, calib)
    images = rng.uniform(0, 1, (9,) + cfg.input_shape).astype(np.float32)
    return params, calib, qnet, images


def _registry(qnet, ids=("m",)):
    reg = ModelRegistry(specs={})
    for i in ids:
        reg.install(i, qnet)
    return reg


# ---------------------------------------------------------------------------
# engine: bit parity + scheduling
# ---------------------------------------------------------------------------
def test_engine_bit_identical_to_direct_forward(served):
    """Acceptance: every completion's int8 capsules equal a direct
    QuantCapsNet.forward on the same image — through a padded bucket."""
    _, _, qnet, images = served
    engine = CapsServeEngine(_registry(qnet), buckets=(2, 4, 8),
                             clock=FakeClock())
    engine.submit_many(images[:5], "m")
    done = engine.drain()
    assert [c.rid for c in done] == [0, 1, 2, 3, 4]
    assert [c.bucket for c in done] == [8] * 5      # 5 pads up to 8

    v = np.asarray(qnet.forward(qnet.quantize_input(
        jnp.asarray(images[:5]))))
    lengths = np.asarray(qnet.class_lengths(jnp.asarray(v)))
    for c in done:
        assert c.v_q.dtype == np.int8
        np.testing.assert_array_equal(c.v_q, v[c.rid])
        np.testing.assert_array_equal(c.lengths, lengths[c.rid])
        assert c.pred == int(np.argmax(lengths[c.rid]))


def test_scheduler_bucketing_and_determinism(served):
    """Waves take the longest same-model run at the head, capped at the
    max bucket; identical submissions replay to identical waves/bits."""
    _, _, qnet, images = served
    reg = _registry(qnet, ids=("m1", "m2"))
    pattern = ["m1", "m1", "m2", "m2", "m2", "m1"]

    def run():
        engine = CapsServeEngine(reg, buckets=(1, 2, 4), clock=FakeClock())
        for img, mid in zip(images, pattern):
            engine.submit(img, mid)
        done = engine.drain()
        return [(c.rid, c.model_id, c.wave, c.bucket) for c in done], \
            [c.v_q for c in done]

    sched1, bits1 = run()
    assert sched1 == [(0, "m1", 0, 2), (1, "m1", 0, 2),
                      (2, "m2", 1, 4), (3, "m2", 1, 4), (4, "m2", 1, 4),
                      (5, "m1", 2, 1)]
    sched2, bits2 = run()
    assert sched1 == sched2
    for a, b in zip(bits1, bits2):
        np.testing.assert_array_equal(a, b)


def test_wave_split_across_buckets(served):
    """More requests than the max bucket split FIFO into several waves,
    each padded to its own bucket."""
    _, _, qnet, images = served
    engine = CapsServeEngine(_registry(qnet), buckets=(2, 4, 8),
                             clock=FakeClock())
    engine.submit_many(images, "m")                  # 9 requests
    done = engine.drain()
    assert [(c.wave, c.bucket) for c in done] == \
        [(0, 8)] * 8 + [(1, 2)]
    m = engine.metrics
    assert m.waves_run == 2 and m.images_done == 9
    assert m.occupancy() == pytest.approx((8 / 8 + 1 / 2) / 2)
    assert m.max_queue_depth() == 9


def test_failed_wave_leaves_queue_intact(served):
    """A raising executable must not drop the wave's requests: the queue
    stays as-is so a later drain can retry them."""
    _, _, qnet, images = served
    reg = _registry(qnet)
    engine = CapsServeEngine(reg, buckets=(4,), clock=FakeClock())
    engine.submit_many(images[:3], "m")
    orig, calls = reg.executable, {"n": 0}

    def flaky(model_id, bucket):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("transient compile failure")
        return orig(model_id, bucket)

    reg.executable = flaky
    with pytest.raises(RuntimeError):
        engine.step()
    assert engine.queue_depth() == 3
    done = engine.drain()                        # retry succeeds
    assert [c.rid for c in done] == [0, 1, 2]


def test_engine_validates_inputs(served):
    _, _, qnet, images = served
    engine = CapsServeEngine(_registry(qnet), buckets=(1, 4))
    with pytest.raises(KeyError):
        engine.submit(images[0], "nope")
    with pytest.raises(ValueError):
        engine.submit(images[0][:16], "m")
    with pytest.raises(ValueError):
        CapsServeEngine(_registry(qnet), buckets=())
    with pytest.raises(ValueError):
        CapsServeEngine(_registry(qnet), buckets=(0, 4))
    assert engine.step() == []                       # idle engine


# ---------------------------------------------------------------------------
# registry: lazy PTQ + executable cache
# ---------------------------------------------------------------------------
def test_registry_lazy_quantize_and_executable_reuse(served):
    _, _, qnet, images = served
    reg = ModelRegistry(specs={"tiny": ModelSpec(
        "tiny", CIFAR10, dataset="uniform", calib_n=8)})
    assert reg.quantize_count == 0                   # lazy until requested
    # static geometry queries (submit-time shape validation) must not
    # trigger the PTQ build either
    assert reg.input_shape("tiny") == tuple(CIFAR10.input_shape)
    assert reg.quantize_count == 0
    engine = CapsServeEngine(reg, buckets=(4,), clock=FakeClock())
    engine.submit_many(images[:3], "tiny")
    engine.drain()
    assert reg.quantize_count == 1
    assert reg.compile_count == 1

    # second wave of the same bucket: no new PTQ, no new executable
    engine.submit_many(images[3:6], "tiny")
    engine.drain()
    assert reg.quantize_count == 1
    assert reg.compile_count == 1
    assert reg.exec_hits >= 1
    assert reg.executable("tiny", 4) is reg.executable("tiny", 4)

    # a new bucket is a new executable, same model
    reg.executable("tiny", 2)
    assert reg.compile_count == 2 and reg.quantize_count == 1

    with pytest.raises(KeyError):
        reg.model("missing")


def test_install_invalidates_stale_executables(served):
    """Re-installing a model under an id must drop wave executables that
    hold the previous model's weights as baked-in constants."""
    _, _, qnet, images = served
    reg = _registry(qnet)
    e1 = reg.executable("m", 2)
    q2 = qnet.with_softmax("precise")
    reg.install("m", q2)
    e2 = reg.executable("m", 2)
    assert e2 is not e1
    x = np.zeros((2,) + tuple(CIFAR10.input_shape), np.float32)
    x[:2] = images[:2]
    np.testing.assert_array_equal(
        np.asarray(e2(x)[0]),
        np.asarray(q2.forward(q2.quantize_input(jnp.asarray(x)))))


# ---------------------------------------------------------------------------
# sharded execution
# ---------------------------------------------------------------------------
def test_sharded_wave_bit_parity_on_1device_mesh(served):
    """Acceptance: serving/sharded.py under a 1-device mesh returns the
    same bits as the unsharded path — both standalone and end-to-end
    through an engine whose registry carries the mesh."""
    _, _, qnet, images = served
    mesh = make_host_mesh(("pod", "data", "model"))
    x = np.zeros((4,) + tuple(CIFAR10.input_shape), np.float32)
    x[:3] = images[:3]
    plain, meshed = compile_wave(qnet, 4), compile_wave(qnet, 4, mesh=mesh)
    for a, b in zip(plain(x), meshed(x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    reg = _registry(qnet)
    reg.mesh = mesh
    engine = CapsServeEngine(reg, buckets=(4,), clock=FakeClock())
    engine.submit_many(images[:3], "m")
    done = engine.drain()
    v = np.asarray(qnet.forward(qnet.quantize_input(
        jnp.asarray(images[:3]))))
    for c in done:
        np.testing.assert_array_equal(c.v_q, v[c.rid])


@pytest.mark.slow
def test_sharded_wave_bit_parity_on_8device_mesh():
    """The wave really splits over the BATCH axes of a multi-device mesh
    (forced-host-device subprocess, same pattern as test_distributed) and
    still matches the unsharded bits."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.nn import CIFAR10, CapsPipeline
        from repro.serving import compile_wave

        pipe = CapsPipeline.from_config(CIFAR10)
        params = pipe.init(jax.random.key(0))
        rng = np.random.default_rng(3)
        calib = jnp.asarray(rng.uniform(
            0, 1, (8,) + CIFAR10.input_shape).astype(np.float32))
        qnet = pipe.quantize(params, calib)
        mesh = Mesh(np.asarray(jax.devices()).reshape(1, 8, 1),
                    ("pod", "data", "model"))
        x = rng.uniform(0, 1, (8,) + CIFAR10.input_shape).astype(np.float32)
        plain, meshed = compile_wave(qnet, 8), compile_wave(qnet, 8, mesh=mesh)
        assert not meshed.in_sharding.is_fully_replicated  # really split
        for a, b in zip(plain(x), meshed(x)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the Pallas kernels run per device on their own rows (shard_map)
        pallas = compile_wave(qnet.with_backend("pallas"), 8, mesh=mesh)
        for a, b in zip(plain(x), pallas(x)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """) % SRC
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert "OK" in out.stdout, (out.stdout[-1000:], out.stderr[-3000:])


# ---------------------------------------------------------------------------
# satellites: with_softmax plan edit, class_lengths out_frac, calibrate
# ---------------------------------------------------------------------------
def test_with_softmax_is_a_pure_plan_edit(served):
    params, calib, qnet, images = served
    q2 = qnet.with_softmax("precise")
    # original untouched; every RoutingPlan flipped; conv plans untouched
    assert qnet.plan["caps"].softmax_impl == "q7"
    for name, p in q2.plan.layers.items():
        if isinstance(p, RoutingPlan):
            assert p.softmax_impl == "precise"
        else:
            assert p is qnet.plan.layers[name]

    # the edit is equivalent to building the pipeline with that softmax
    pipe2 = CapsPipeline.from_config(CIFAR10, softmax_impl="precise")
    qnet2 = pipe2.quantize(params, calib)
    xq = qnet.quantize_input(jnp.asarray(images[:2]))
    np.testing.assert_array_equal(np.asarray(q2.forward(xq)),
                                  np.asarray(qnet2.forward(xq)))
    # and round-trips back to the original bits
    np.testing.assert_array_equal(
        np.asarray(q2.with_softmax("q7").forward(xq)),
        np.asarray(qnet.forward(xq)))


def test_class_lengths_uses_plan_out_frac(served):
    """Regression for the hardcoded /128: a non-default squash_out_frac
    must rescale class lengths by its own 2^-out_frac."""
    _, _, qnet, images = served
    xq = qnet.quantize_input(jnp.asarray(images[:2]))
    def ref_lengths(v, out_frac):
        ss = np.sum(np.asarray(v, np.int64) ** 2, -1).astype(np.float32)
        return np.sqrt(ss) * np.float32(2.0 ** -out_frac)

    v7 = qnet.forward(xq)
    np.testing.assert_array_equal(np.asarray(qnet.class_lengths(v7)),
                                  ref_lengths(v7, 7))

    plan6 = dataclasses.replace(
        qnet.plan, layers={**qnet.plan.layers, "caps": dataclasses.replace(
            qnet.plan.layers["caps"], squash_out_frac=6)})
    q6 = dataclasses.replace(qnet, plan=plan6)
    assert q6.plan["caps"].out_frac == 6
    v6 = q6.forward(xq)
    np.testing.assert_array_equal(np.asarray(q6.class_lengths(v6)),
                                  ref_lengths(v6, 6))
    # Q0.6 lengths land near the Q0.7 ones once both are dequantized
    np.testing.assert_allclose(np.asarray(q6.class_lengths(v6)),
                               np.asarray(qnet.class_lengths(v7)),
                               atol=0.15)
    # the pallas backend falls back to the oracle loop off the Q0.7 plan
    np.testing.assert_array_equal(
        np.asarray(q6.with_backend("pallas").forward(xq)), np.asarray(v6))


def test_calibrate_device_side_accumulation_matches(served):
    """The single-sync calibrate must reproduce the per-batch max|x|
    semantics, including a partial trailing batch."""
    params, calib, qnet, _ = served
    pipe = qnet.pipeline
    stats_batched = pipe.calibrate(params, calib[:10], batch=4)
    stats_single = pipe.calibrate(params, calib[:10], batch=10)
    assert set(stats_batched.max_abs) == set(stats_single.max_abs)
    for k, v in stats_single.max_abs.items():
        assert stats_batched[k] == pytest.approx(v, rel=1e-6), k
    # and against an unjitted reference walk
    _, taps = pipe.forward(params, calib[:10], with_taps=True)
    for k, t in taps.items():
        assert stats_batched[k] == pytest.approx(
            float(jnp.max(jnp.abs(t))), rel=1e-5), k


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_metrics_accounting():
    m = ServeMetrics()
    m.record_submit(0.0, 1)
    m.record_submit(0.5, 2)
    m.record_wave(bucket=8, n_real=4, exec_s=2.0, t_done=4.0,
                  latencies_s=[1.0, 2.0, 3.0, 4.0])
    m.record_wave(bucket=2, n_real=1, exec_s=1.0, t_done=10.0,
                  latencies_s=[5.0])
    assert m.images_done == 5 and m.waves_run == 2
    assert m.latency_percentile(50) == pytest.approx(3.0)
    assert m.latency_percentile(99) == pytest.approx(4.96)
    assert m.occupancy() == pytest.approx((0.5 + 0.5) / 2)
    assert m.images_per_s() == pytest.approx(5 / 10.0)   # wall 0 -> 10
    assert m.max_queue_depth() == 2
    assert "5 imgs in 2 waves" in m.report()

    empty = ServeMetrics()
    assert np.isnan(empty.latency_percentile(50))
    assert np.isnan(empty.images_per_s())
