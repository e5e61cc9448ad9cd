"""Check `int8_ops.isqrt` as the TPU computes it, in a compiled Pallas kernel.

    python benchmarks/check_isqrt.py             # on a TPU: every n < 2^24
    python benchmarks/check_isqrt.py --bits 16   # quicker (interpret mode on CPU)

`isqrt` seeds the integer square root with a float32 sqrt, so its exactness
rests on the chip's sqrt and float -> int conversion being within one of the
true root.  This runs it in a Mosaic kernel (interpret mode off the TPU) over
every int32 n in [0, 2^bits) and every k^2 - 1, k^2, k^2 + 1 up to 2^31 - 1,
and compares with the NumPy mirror of Alg. 4's Newton loop.  It prints the
device and the count of mismatches, and exits 1 if that count is not 0.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ops import default_interpret
from repro.nn.variants import _np_isqrt_newton
from repro.quant.int8_ops import isqrt

LANES, ROWS = 128, 512


def _isqrt_kernel(n_ref, o_ref):
    o_ref[...] = isqrt(n_ref[...])


def isqrt_pallas(n: np.ndarray, interpret: bool) -> np.ndarray:
    """isqrt of a flat int32 array in a Pallas kernel of (512, 128) tiles."""
    tile = ROWS * LANES
    padded = np.zeros(-(-n.size // tile) * tile, np.int32)
    padded[:n.size] = n
    x = jnp.asarray(padded.reshape(-1, LANES))
    spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _isqrt_kernel, grid=(x.shape[0] // ROWS,), in_specs=[spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        interpret=interpret)(x)
    return np.asarray(out).reshape(-1)[:n.size]


def squares_pm1() -> np.ndarray:
    k = np.arange(1, 46341, dtype=np.int64)
    n = np.concatenate([k * k - 1, k * k, k * k + 1, [2 ** 31 - 1]])
    return n[n < 2 ** 31].astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bits", type=int, default=24,
                    help="check every n in [0, 2^bits) (default 24)")
    args = ap.parse_args(argv)
    interpret = default_interpret()
    dev = jax.devices()[0]
    checks = {}
    for name, n in [(f"below_2^{args.bits}",
                     np.arange(1 << args.bits, dtype=np.int32)),
                    ("squares_pm1", squares_pm1())]:
        got = isqrt_pallas(n, interpret)
        bad = np.flatnonzero(got != _np_isqrt_newton(n))
        checks[name] = {"checked": int(n.size), "mismatches": int(bad.size),
                        "first": [int(v) for v in n[bad[:8]]]}
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "interpret": interpret, **checks}))
    return 0 if all(c["mismatches"] == 0 for c in checks.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
