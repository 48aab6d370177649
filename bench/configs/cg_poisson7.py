"""The cell of the ``cg_poisson7`` configuration: sets of CG iterations through
``hpc.cg.cg_solve_bell`` on the 7-point Poisson operator in Blocked-ELL.

Traffic keys (``bench/traffic/*.json``): ``grid`` (n, for n^3 unknowns);
``set_iterations``, the iterations of one set (no early stop: tol = 0);
``rhs_pool``, the right-hand sides drawn at set-up (set i uses b[i %
rhs_pool]); ``check_sets``, how many of the window's sets are checked.

One closed loop: each set starts from x0 = 0 and runs to its last
iteration before the next begins.  The window's sets are sampled by a
reservoir drawn from the seed and, after the window, each sampled iterate is
compared with the reference (``cg_poisson7_ref``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import harness
from bench.configs import cg_poisson7_ref as ref
from bench.draw import normal_f64, seed_key


def poisson_bell(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """7-point -Laplacian_h on an n^3 grid (zero Dirichlet), Blocked-ELL with
    bw = 7: slot 0 the diagonal (6), slots 1-6 the neighbours (-1) along
    axes 0, 1, 2 at -1 then +1; slots past the boundary point at the row
    itself with value 0.  (The layout of ``chip_smoke.poisson_bell``.)"""
    rows = np.arange(n ** 3)
    val = np.zeros((n ** 3, 7))
    col = np.repeat(rows[:, None], 7, axis=1).astype(np.int32)
    val[:, 0] = 6.0
    coord = (rows // (n * n), rows // n % n, rows % n)
    slot = 1
    for ax, stride in enumerate((n * n, n, 1)):
        for d in (-1, 1):
            inside = (coord[ax] + d >= 0) & (coord[ax] + d < n)
            col[inside, slot] = rows[inside] + d * stride
            val[inside, slot] = -1.0
            slot += 1
    return val, col


class Cell:
    """One closed-loop caller of the CG solver."""

    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.seed = seed
        self.n = int(traffic["grid"])
        self.iters = int(traffic["set_iterations"])
        self.pool = int(traffic["rhs_pool"])
        self.kept = harness.Reservoir(int(traffic["check_sets"]),
                                      harness.rng_for(seed, 1))

    def setup(self) -> None:
        with jax.profiler.TraceAnnotation("bench.draw"):
            val, col = poisson_bell(self.n)
            self.val, self.col = jax.device_put(val), jax.device_put(col)
            self.b = jax.block_until_ready(normal_f64(
                seed_key(self.seed), ((self.n ** 3,),) * self.pool))

    def _solve(self, b, iters: int):
        from repro.hpc import cg
        return cg.cg_solve_bell(self.val, self.col, b, tol=0.0, maxiter=iters,
                                record_plain=False)

    def warm(self) -> List[str]:
        from repro.core import dispatch

        jax.block_until_ready(self._solve(self.b[0], 2).x)
        route = dispatch.choose_route(dispatch.get_plan(7, margin_bits=4),
                                      "spmv_bell", None, shape=self.val.shape)
        hlo = jax.jit(lambda v, c, x: dispatch.spmv(v, c, x)).lower(
            self.val, self.col, self.b[0]).as_text()
        return [f"kind=spmv_bell route={route} "
                f"tpu_custom_call={'tpu_custom_call' in hlo}"]

    def step(self, i: int) -> int:
        j = i % self.pool
        with jax.profiler.TraceAnnotation("bench.cg_set"):
            res = self._solve(self.b[j], self.iters)
            x = jax.block_until_ready(res.x)
        if res.iters != self.iters:
            raise RuntimeError(f"set ran {res.iters} iterations, not {self.iters}")
        self.kept.offer((j, x))
        return res.iters

    def work(self) -> Tuple[float, float]:
        return ref.work(self.n ** 3, 7)

    def end_to_end(self, units: int, window_s: float) -> Dict[str, float]:
        return {"cg_iter_ms": 1e3 * window_s / units}

    def check(self, control: bool = False) -> Dict[str, float]:
        """Compare the sampled iterates with the reference; with
        ``control``, the control's iterates stand in for them."""
        pairs = []
        for j, x in self.kept.items:
            b = self.b[j]
            got = ref.control(b, self.n, self.iters) if control else np.asarray(x)
            pairs.append((got, np.asarray(b)))
        self.val = self.col = self.b = None
        self.kept.items = []
        errs = [ref.rel_err(x, ref.cg(b, self.n, self.iters)) for x, b in pairs]
        return {"x_rel_err": float(np.max(errs))}
