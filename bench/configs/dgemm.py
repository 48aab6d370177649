"""The cell of the ``dgemm`` configuration: C = A B through
``dispatch.matmul``.

Traffic keys (``bench/traffic/*.json``): ``m``, ``k``, ``n``; ``a_pool`` and
``b_pool``, the numbers of distinct A and B drawn at set-up (call i uses
A[i % a_pool] and B[i % b_pool], so no call repeats the previous call's
operands); ``check_calls``, how many of the window's answers are checked;
``check_band``, the band width of the check's row and column draw.

One closed loop with one call in flight.  The window's answers are sampled
by a reservoir drawn from the seed; after the window, one row in every band
of rows and one column in every band of columns of each sampled answer are
compared with the long-double reference (``dgemm_ref``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import harness
from bench.draw import normal_f64, seed_key
from bench.configs import dgemm_ref as ref


class Cell:
    """One closed-loop caller of the emulated DGEMM."""

    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.seed = seed
        t = traffic
        self.m, self.k, self.n = int(t["m"]), int(t["k"]), int(t["n"])
        self.a_pool, self.b_pool = int(t["a_pool"]), int(t["b_pool"])
        self.band = int(t["check_band"])
        self.kept = harness.Reservoir(int(t["check_calls"]),
                                      harness.rng_for(seed, 1))

    def setup(self) -> None:
        from repro.core import dispatch

        shapes = ((self.m, self.k),) * self.a_pool + ((self.k, self.n),) * self.b_pool
        with jax.profiler.TraceAnnotation("bench.draw"):
            ops = jax.block_until_ready(normal_f64(seed_key(self.seed), shapes))
        self.a, self.b = ops[:self.a_pool], ops[self.a_pool:]
        self.fn = jax.jit(lambda a, b: dispatch.matmul(a, b))

    def warm(self) -> List[str]:
        from repro.core import dispatch

        jax.block_until_ready(self.fn(self.a[0], self.b[0]))
        kind = "gemv" if self.n <= dispatch.GEMV_MAX_B else "gemm"
        route = dispatch.choose_route(dispatch.get_plan(self.k), kind, None,
                                      shape=(self.m, self.k, self.n))
        hlo = self.fn.lower(self.a[0], self.b[0]).as_text()
        return [f"kind={kind} route={route} "
                f"tpu_custom_call={'tpu_custom_call' in hlo}"]

    def step(self, i: int) -> int:
        ai, bi = i % self.a_pool, i % self.b_pool
        with jax.profiler.TraceAnnotation("bench.call"):
            c = jax.block_until_ready(self.fn(self.a[ai], self.b[bi]))
        self.kept.offer((ai, bi, c))
        return 1

    def work(self) -> Tuple[float, float]:
        return ref.work(self.m, self.k, self.n)

    def end_to_end(self, units: int, window_s: float) -> Dict[str, float]:
        return {"fp64_gflops": units * self.work()[0] / window_s / 1e9}

    def check(self, control: bool = False) -> Dict[str, float]:
        """Compare the sampled answers with the reference; with ``control``,
        the control's answers to the same operands stand in for them."""
        rng = harness.rng_for(self.seed, 2)
        blocks = []
        for ai, bi, c in self.kept.items:
            rows = ref.bands(rng, self.m, self.band)
            cols = ref.bands(rng, self.n, self.band)
            a, b = self.a[ai], self.b[bi]
            if control:
                c = ref.control(a, b)
            blocks.append((np.asarray(c[rows])[:, cols], np.asarray(a[rows]),
                           np.asarray(b[:, cols])))
        self.a = self.b = self.fn = None
        self.kept.items = []
        return {"max_err": float(np.max([ref.max_err(*blk) for blk in blocks]))}
