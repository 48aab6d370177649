"""The cell of the ``cg_npb`` configuration: NPB CG's outer steps through
``hpc.npb_cg.power_step`` (``cg_solve_bell`` on NPB's random sparse matrix in
Blocked-ELL, then rnorm, zeta and the normalisation).

Traffic keys (``bench/traffic/*.json``): ``class``, the NPB class, with its
``na``, ``nonzer``, ``shift`` and ``rcond`` (checked against the program's
class table); ``cg_iterations``, the CG iterations of one outer step;
``check_steps``, how many of the window's steps are checked.

One closed loop: each step starts from the last step's x, from x = 1 at the
first and again after each of the class's ``niter`` steps, as NPB's timed
loop runs.  NPB's matrix does not depend on the seed; the seed draws the
reservoir of checked steps.  After the window, the CSR matrix is held to the
configuration's digest of NPB's verified build, and each sampled step is
compared with the reference (``cg_npb_ref``), which replays the steps from
x = 1 on that matrix.  At class B a step outlasts the window, so a run
checks step 0 alone, and every seed reads the same numbers.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.configs import cg_npb_ref as ref


class Cell:
    """One closed-loop caller of NPB CG's inverse power iteration."""

    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.traffic = traffic
        self.digests = config["csr_sha256"]
        self.cls = str(traffic["class"])
        self.iters = int(traffic["cg_iterations"])
        self.kept = harness.Reservoir(int(traffic["check_steps"]),
                                      harness.rng_for(seed, 1))

    def setup(self) -> None:
        from repro.hpc import npb_cg, spmv_formats

        c = npb_cg.CLASSES[self.cls]
        for key in ("na", "nonzer", "shift", "rcond"):
            if float(self.traffic[key]) != float(getattr(c, key)):
                raise ValueError(f"traffic {key}={self.traffic[key]!r}, NPB class "
                                 f"{self.cls} has {getattr(c, key)!r}")
        self.shift, self.niter = c.shift, c.niter
        with jax.profiler.TraceAnnotation("bench.draw"):
            self.csr = npb_cg.makea(c)
            val, col = spmv_formats.csr_to_blocked_ell(*self.csr)
            self.val, self.col = jax.device_put(val), jax.device_put(col)
            self.ones = jax.block_until_ready(jnp.ones(c.na, self.val.dtype))
        self.x = self.ones

    def _step(self, x, iters: int):
        from repro.hpc import npb_cg
        return npb_cg.power_step(self.val, self.col, x, self.shift,
                                 cg_iters=iters)

    def warm(self) -> List[str]:
        from repro.core import dispatch
        from repro.hpc import spmv_formats

        jax.block_until_ready(self._step(self.ones, 2))
        na, bw = self.val.shape
        nnz = int(self.csr[0][-1])
        route = dispatch.choose_route(dispatch.get_plan(bw, margin_bits=4),
                                      "spmv_bell", None, shape=self.val.shape)
        hlo = jax.jit(lambda v, c, x: dispatch.spmv(v, c, x)).lower(
            self.val, self.col, self.ones).as_text()
        return [f"kind=spmv_bell route={route} "
                f"tpu_custom_call={'tpu_custom_call' in hlo} "
                f"banded={spmv_formats.band_offsets(self.val, self.col) is not None}",
                f"class={self.cls} na={na} nnz={nnz} bw={bw} "
                f"rho={na * bw / nnz!r}"]

    def step(self, i: int) -> int:
        k = i % self.niter
        x = self.ones if k == 0 else self.x
        with jax.profiler.TraceAnnotation("bench.npb_step"):
            self.x, zeta, _ = jax.block_until_ready(self._step(x, self.iters))
        self.kept.offer((k, self.x, zeta))
        return self.iters

    def work(self) -> Tuple[float, float]:
        return ref.work(self.val.shape[0], int(self.csr[0][-1]))

    def end_to_end(self, units: int, window_s: float) -> Dict[str, float]:
        return {"cg_iter_ms": 1e3 * window_s / units}

    def check(self, control: bool = False) -> Dict[str, float]:
        """Compare the sampled steps with the reference's replay; with
        ``control``, the control's steps stand in for them."""
        got = {k: (np.asarray(x), float(zeta)) for k, x, zeta in self.kept.items}
        self.val = self.col = self.x = self.ones = None
        self.kept.items = []
        ref.check_matrix(*self.csr, self.digests[self.cls])
        want = ref.replay(*self.csr, self.shift, self.iters, got)
        if control:
            got = ref.control(*self.csr, self.shift, self.iters, got)
        return {"z_rel_err": float(np.max([ref.rel_err(got[k][0], want[k][0])
                                           for k in got])),
                "zeta_rel_err": float(np.max([abs(got[k][1] - want[k][1])
                                              / abs(want[k][1]) for k in got]))}
