"""Wave execution: one compiled function per (model, bucket), optionally
split across a device mesh.

`wave_fn` is the single definition of what a serving wave computes —
quantize the float images, run the int8 pipeline (`CapsPipeline
.forward_q7` via `QuantCapsNet.forward`), score class lengths, argmax —
with `dist.api.shard` constraints on the logical BATCH axis at the wave
boundary.  Under a mesh, GSPMD splits the wave's rows across the BATCH
(pod x data) axes; with no mesh (or a 1-device mesh) `api.shard` degrades
to the identity and the very same function runs locally.  The Pallas
kernels, which GSPMD cannot split, run on each device's own rows
(`api.batch_local`, called by the pallas backend).  Because every
int8 op is exact and rows are independent, the sharded wave is
bit-identical to the unsharded one.

`compile_wave` AOT-compiles (jit -> lower -> compile) so the registry's
executable cache holds real XLA executables keyed on (model, backend,
bucket): a wave never pays a trace, and a cache hit is observable (the
registry counts compiles).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.dist import api


def wave_fn(qnet):
    """float images [B,H,W,C] -> (v_q int8 [B,J,O], lengths [B,J],
    pred int32 [B]) with logical-BATCH sharding constraints."""
    def fn(x):
        x = api.shard(x, api.BATCH)
        x_q = qnet.quantize_input(x)
        v_q = qnet.forward(x_q)
        v_q = api.shard(v_q, api.BATCH)
        lengths = qnet.class_lengths(v_q)
        pred = jnp.argmax(lengths, axis=-1).astype(jnp.int32)
        return v_q, lengths, pred
    return fn


@dataclasses.dataclass(frozen=True)
class CompiledWave:
    """An AOT-compiled wave executable pinned to one input shape."""
    compiled: object                 # jax.stages.Compiled
    in_sharding: object | None       # None off-mesh
    bucket: int
    input_shape: tuple               # (bucket, H, W, C)

    def put(self, x) -> jax.Array:
        """The host->device copy of a padded batch, placed with the
        executable's input sharding (the default device off-mesh)."""
        x = jnp.asarray(x, jnp.float32)
        if x.shape != self.input_shape:
            raise ValueError(
                f"wave executable compiled for {self.input_shape}, "
                f"got {x.shape}")
        if self.in_sharding is not None:
            x = jax.device_put(x, self.in_sharding)
        return x

    def __call__(self, x):
        """Dispatch the wave on `x`, a host batch or the device array
        `put` returned; the outputs are device arrays, possibly still
        being computed."""
        if not isinstance(x, jax.Array):
            x = self.put(x)
        return self.compiled(x)


def compile_wave(qnet, bucket: int, mesh=None) -> CompiledWave:
    """Compile `wave_fn(qnet)` for a fixed bucket, under `mesh` if given.

    The mesh only needs to be active while tracing: `api.shard` resolves
    the logical spec against it and the constraint is baked into the
    executable, so callers invoke the result without a mesh context.
    """
    shape = (bucket,) + tuple(qnet.pipeline.cfg.input_shape)
    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    if mesh is None:
        compiled = jax.jit(wave_fn(qnet)).lower(spec).compile()
        in_sh = None
    else:
        with mesh:
            compiled = jax.jit(wave_fn(qnet)).lower(spec).compile()
        in_sh = compiled.input_shardings[0][0]
    return CompiledWave(compiled=compiled, in_sharding=in_sh,
                        bucket=bucket, input_shape=shape)
