"""Emulated attention benchmarks — the seam's fifth kind, both routes.

Rows (name,us_per_call,derived,route,shape_class):
  kernel_attention/route_<mode>/us        — prefill (S = T) wall-clock per
                                            route; derived on both rows of the
                                            pair = max |pallas - xla| over the
                                            outputs, expected exactly 0 (the
                                            online-softmax scan over the Pallas
                                            GEMM kernels and over
                                            emulated_matmul are bit-identical
                                            by construction).
  kernel_attention/decode_route_<mode>/us — same contract at the serving
                                            decode shape (S = 1 against a T
                                            deep cache).

Wall-clock on CPU measures the interpreter for the pallas route (machinery
check, not TPU perf) — the point of this section is the route-parity derived
column and the provenance (route, shape_class) telemetry attaches, which the
perf-trajectory CI records in both legs of the ``REPRO_DISPATCH`` matrix.
"""

from __future__ import annotations

from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from benchmarks.kernels import _provenance, _timed

Row = Tuple[str, float, float, str, str]


def attention_section() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)

    # --- prefill shape (S = T): causal mask, both routes -----------------------
    S, D = 64, 32
    q = jnp.asarray(rng.standard_normal((S, D)))
    k = jnp.asarray(rng.standard_normal((S, D)))
    v = jnp.asarray(rng.standard_normal((S, D)))
    causal = jnp.tril(jnp.ones((S, S), jnp.int8))
    pre = {}
    # reps=1 throughout: one emulated attention call costs seconds on CPU
    # (both routes run the full residue pipeline per kv block), and the smoke
    # lane runs this section in both REPRO_DISPATCH legs.
    for mode in ("xla", "pallas"):
        us = _timed(lambda mode=mode: dispatch.attention(
            q, k, v, mask=causal, mode=mode), reps=1)
        route, cls = _provenance(lambda mode=mode: dispatch.attention(
            q, k, v, mask=causal, mode=mode))
        pre[mode] = (f"kernel_attention/route_{mode}/us", us,
                     dispatch.attention(q, k, v, mask=causal, mode=mode),
                     route, cls)
    diff = float(jnp.max(jnp.abs(pre["pallas"][2] - pre["xla"][2])))
    rows.extend((name, us, diff, route, cls)
                for name, us, _, route, cls in pre.values())

    # --- decode shape (S = 1, deep cache): padding mask, both routes -----------
    T = 96
    qd = jnp.asarray(rng.standard_normal((1, D)))
    kd = jnp.asarray(rng.standard_normal((T, D)))
    vd = jnp.asarray(rng.standard_normal((T, D)))
    valid = jnp.asarray((np.arange(T) < 80).astype(np.int8))[None, :]
    dec = {}
    for mode in ("xla", "pallas"):
        us = _timed(lambda mode=mode: dispatch.attention(
            qd, kd, vd, mask=valid, mode=mode), reps=1)
        route, cls = _provenance(lambda mode=mode: dispatch.attention(
            qd, kd, vd, mask=valid, mode=mode))
        dec[mode] = (f"kernel_attention/decode_route_{mode}/us", us,
                     dispatch.attention(qd, kd, vd, mask=valid, mode=mode),
                     route, cls)
    diff = float(jnp.max(jnp.abs(dec["pallas"][2] - dec["xla"][2])))
    rows.extend((name, us, diff, route, cls)
                for name, us, _, route, cls in dec.values())
    return rows
