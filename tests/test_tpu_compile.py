"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached.

Interpret mode (every other kernel test) runs the kernel body as plain
JAX and cannot see what Mosaic refuses: dot shapes it cannot lower,
layouts that exceed the scoped VMEM.  These tests hand the real
geometries to the TPU compiler.  They run nothing, so they say nothing
about results or time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.routing import routing3d_q7_pallas, routing_q7_pallas
from repro.nn.config import CIFAR10, MNIST, SMALLNORB
from repro.serving import EDGE_TINY, ModelRegistry
from repro.serving.sharded import compile_wave, wave_fn

GEOMETRIES = {"mnist": MNIST, "smallnorb": SMALLNORB, "cifar10": CIFAR10,
              "edge_tiny": EDGE_TINY}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables are written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cifar10_pallas():
    return ModelRegistry().model("cifar10@pallas")


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_routing_kernel_compiles(one_chip, geometry, batch):
    cfg = GEOMETRIES[geometry]
    r = cfg.routings
    u_hat = jax.ShapeDtypeStruct(
        (batch, cfg.num_classes, cfg.num_input_caps, cfg.caps_dim),
        jnp.int8, sharding=one_chip)

    def route(u):
        return routing_q7_pallas(
            u, num_iters=r, caps_out_shifts=(6,) * r,
            caps_out_fracs=(5,) * r, agree_shifts=(7,) * (r - 1),
            logit_frac=5, rounding="floor", interpret=False)

    compiled = jax.jit(route).lower(u_hat).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_scoped_vmem(hlo: str, kernel: str) -> int:
    """The scoped VMEM the compiler gave the Mosaic kernel `kernel`."""
    line, = [ln for ln in hlo.splitlines()
             if re.match(rf"\s*(ROOT )?%{kernel}\.\d+ = ", ln)
             and 'custom_call_target="tpu_custom_call"' in ln]
    size, = re.findall(r'used_scoped_memory_configs\\?":\[\{[^}]*'
                       r'\\?"size\\?":\\?"(\d+)', line)
    return int(size)


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("layer", ["class", "local"])
def test_deepcaps_routing_kernels_compile(one_chip, layer, batch):
    """DeepCaps' class routing (J=10, I=2,560, O=32: about 3.3 MB of
    int32 u_hat a sample) and its local routing with a bias (J=32,
    I=32, O=8, 16 groups an image), each under its own kernel name,
    within the default 16 MiB of scoped VMEM."""
    kernel, (J, I, O), groups = {
        "class": (routing_q7_pallas, (10, 2560, 32), batch),
        "local": (routing3d_q7_pallas, (32, 32, 8), 16 * batch)}[layer]
    u_hat = jax.ShapeDtypeStruct((groups, J, I, O), jnp.int8,
                                 sharding=one_chip)
    bias = jax.ShapeDtypeStruct((J, O), jnp.int32, sharding=one_chip) \
        if layer == "local" else None

    def route(u, b):
        return kernel(u, b, num_iters=3, caps_out_shifts=(6,) * 3,
                      caps_out_fracs=(5,) * 3, agree_shifts=(7,) * 2,
                      logit_frac=5, rounding="floor", interpret=False)

    hlo = jax.jit(route).lower(u_hat, bias).compile().as_text()
    vmem = _kernel_scoped_vmem(hlo, kernel.__name__)
    assert 0 < vmem < 16 * 2**20


@pytest.mark.parametrize("rows_dim", [(10, 6), (256, 4), (64, 4),
                                      (1024, 4), (64 * 64, 4),
                                      (64 * 1024, 4)])
def test_squash_kernel_compiles(one_chip, rows_dim):
    s = jax.ShapeDtypeStruct(rows_dim, jnp.int8, sharding=one_chip)
    compiled = jax.jit(
        lambda x: ops.squash_q7(x, in_frac=5, interpret=False)
    ).lower(s).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # lane-dense: the kernel's operand has the capsules on its minor
    # (lane) dimension, 128 wide, and not the D components
    (dims, layout), = re.findall(
        r"operand_layout_constraints=\{s8\[([\d,]+)\]\{([\d,]+)\}\}", hlo)
    minor = int(layout.split(",")[0])
    assert int(dims.split(",")[minor]) == 128 != rows_dim[-1]


def test_pallas_wave_compiles_cifar10(one_chip, cifar10_pallas,
                                      monkeypatch):
    """A whole `cifar10@pallas` serving wave at bucket 64: XLA's int8
    convs and u_hat einsum around the two Mosaic kernels."""
    x = jax.ShapeDtypeStruct((64,) + CIFAR10.input_shape, jnp.float32,
                             sharding=one_chip)
    # this process's backend is the CPU, where the kernels would trace
    # in interpret mode; the wave is compiled for the TPU
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    compiled = jax.jit(wave_fn(cifar10_pallas)).lower(x).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # each layer's named scope reaches the compiled ops' metadata, with
    # the capsule phases inside the capsule layers
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = [f"/{l.name}/" for l in cifar10_pallas.pipeline.layers]
    pcap, caps = (l.name for l in cifar10_pallas.pipeline.layers[-2:])
    scopes += [f"/{pcap}/squash/", f"/{caps}/uhat/", f"/{caps}/routing/"]
    for scope in scopes:
        assert any(scope in n for n in op_names), scope
    # the kernels keep their names, which the benchmark's op classes
    # are keyed on
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', hlo)
    assert any("routing" in k for k in kernels), kernels
    assert any("squash" in k for k in kernels), kernels


def test_sharded_pallas_wave_compiles_on_4_chips(topo, cifar10_pallas,
                                                 monkeypatch):
    """The `serve_caps --mesh host` wave over four chips: Mosaic kernels
    cannot be split by the SPMD partitioner, so each chip must run them
    on its own rows of the batch."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("pod", "model", "data"))
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    wave = compile_wave(cifar10_pallas, 64, mesh=mesh)
    assert len(wave.in_sharding.device_set) == 4
    assert not wave.in_sharding.is_fully_replicated
    assert "tpu_custom_call" in wave.compiled.as_text()
