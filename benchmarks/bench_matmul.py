"""Paper Tables 3 & 4 analogue: int8 matrix-multiplication variants.

The paper times mat_mult_q7{,_trb,_simd} on a 20x30 @ 30x40 int8 matmul
(Cortex-M: 1.20-6.35 ms; GAP-8 octa-core: 0.31-0.64 ms).  Here the
variants are the TPU-native decisions: the XLA int8 dot (oracle), the
Pallas kernel in interpret mode (correctness harness; on real TPU the MXU
runs this at 2x bf16 rate), and the fp32 baseline the paper compares
against.  CPU wall times are indicative; the derived column reports
MAC/us.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import util
from benchmarks.util import csv_row, time_call
from repro.kernels import ops
from repro.quant import int8_ops

SHAPES = [(20, 30, 40), (128, 128, 128), (256, 256, 256)]


def main():
    rng = np.random.default_rng(0)
    for (M, K, N) in SHAPES[:1] if util.SMOKE else SHAPES:
        a = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int8)
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
        macs = M * K * N

        f = jax.jit(lambda x, y: int8_ops.matmul_q7(x, y, 7))
        us = time_call(f, a, b)
        csv_row(f"matmul_q7_xla_{M}x{K}x{N}", us, f"{macs/us:.0f}MAC/us")

        us = time_call(lambda x, y: ops.matmul_q7(x, y, 7), a, b)
        csv_row(f"matmul_q7_pallas_interp_{M}x{K}x{N}", us,
                f"{macs/us:.0f}MAC/us")

        g = jax.jit(lambda x, y: x @ y)
        us = time_call(g, af, bf)
        csv_row(f"matmul_fp32_baseline_{M}x{K}x{N}", us,
                f"{macs/us:.0f}MAC/us")


if __name__ == "__main__":
    main()
