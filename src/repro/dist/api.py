"""Logical sharding axes and mesh-aware constraint helpers.

Models speak in LOGICAL axes — `BATCH` (data parallel, spanning the pod
and data mesh axes) and `SEQ` (sequence parallel over the model axis) —
and `fspec` translates a logical spec into a `PartitionSpec` valid for
whatever mesh is active, silently dropping axes the mesh does not have.
That is what lets the same model code run on a ("data", "model") single
pod, a ("pod", "data", "model") multi-pod, or a 1-device test process.
"""
from __future__ import annotations

import numpy as np

import jax
# `with mesh:` records its mesh only in this thread-local stack: the
# public jax.sharding.get_mesh / get_abstract_mesh see jax.set_mesh alone
from jax._src.mesh import thread_resources as _thread_resources
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axes: data parallelism spans pod x data; sequence parallelism
# reuses the model axis (tensor and sequence sharding never coexist on
# the same tensor dimension).
BATCH = ("pod", "data")
SEQ = "model"


def current_mesh() -> Mesh | None:
    """The mesh of the innermost `with mesh:` context, or None."""
    m = _thread_resources.env.physical_mesh
    return None if m.empty else m


def dp_size(mesh) -> int:
    """Total data-parallel ways (product of the BATCH axes present)."""
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in BATCH if a in mesh.axis_names],
                       initial=1))


def fspec(mesh, *axes) -> P:
    """Filter a logical spec down to the axes `mesh` actually has.

    Each entry is None, an axis name, or a tuple of axis names; names not
    in `mesh.axis_names` are dropped.  A tuple that filters down to one
    name collapses to the bare name (PartitionSpec treats them as
    distinct), and to None when nothing survives.
    """
    names = set(mesh.axis_names)
    out = []
    for ax in axes:
        if ax is None:
            out.append(None)
        elif isinstance(ax, (tuple, list)):
            kept = tuple(a for a in ax if a in names)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            out.append(ax if ax in names else None)
    return P(*out)


def batch_local(fn, x):
    """`fn(x)`, with each device of the active mesh applying `fn` to its
    own BATCH rows of `x` (shard_map), for ops the SPMD partitioner
    cannot split, such as Mosaic kernels.  `fn` must act on rows
    independently.  A batch the BATCH axes do not divide is replicated;
    with no mesh active this is plain `fn(x)`."""
    mesh = current_mesh()
    if mesh is None:
        return fn(x)
    spec = fspec(mesh, BATCH) if x.shape[0] % dp_size(mesh) == 0 else P()
    # check_vma=False: a pallas_call states no per-axis variance
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def shard(x, *axes):
    """`with_sharding_constraint(x, fspec(mesh, *axes))` under the active
    mesh; identity when no mesh is active (tests, single device)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, fspec(mesh, *axes)))
