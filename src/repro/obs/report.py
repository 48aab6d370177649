"""Measured-vs-TME report — the paper's falsifiability instrument, pointed at
this repo's own seam.

Aggregates the telemetry counters (live, or a ``telemetry.write_json``
snapshot) into one row per (kind, route): calls, mean measured μs, mean
TME-predicted μs, and the model-error ratio measured/TME.  On this CPU
container the ratio is expected to be large (the reference chip is the TPU
v5e spec and the pallas route runs the kernel interpreter) — the point is the
*trajectory*: the ratio is recorded on every CI run, so the accelerator lane
can tighten it into a real gate (see ``benchmarks.check_regression
--telemetry``).

CLI::

    python -m repro.obs.report                 # built-in sweep, then report
    python -m repro.obs.report telemetry.json  # report a saved snapshot
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

from repro.obs import telemetry

COLUMNS = ("kind", "route", "calls", "mean_us", "tme_us", "ratio")


def _counter_list(snap: Optional[Dict[str, Any]] = None) -> List[Dict[str, Any]]:
    if snap is None:
        snap = telemetry.snapshot()
    return snap.get("counters", [])


def table_rows(snap: Optional[Dict[str, Any]] = None) -> List[Dict[str, Any]]:
    """One row per (kind, route), aggregated over shape classes.

    ``ratio`` is total-measured / total-TME-predicted μs (0.0 when the kind
    has no prediction — solver/serving events).  Rows sort by kind, route.
    """
    agg: Dict[tuple, Dict[str, float]] = {}
    for c in _counter_list(snap):
        key = (c["kind"], c["route"])
        a = agg.setdefault(key, {"calls": 0, "us": 0.0, "tme_us": 0.0})
        a["calls"] += int(c["calls"])
        a["us"] += float(c["us"])
        a["tme_us"] += float(c["tme_us"])
    rows = []
    for (kind, route), a in sorted(agg.items()):
        calls = max(a["calls"], 1)
        rows.append({
            "kind": kind, "route": route, "calls": a["calls"],
            "mean_us": a["us"] / calls,
            "tme_us": a["tme_us"] / calls,
            "ratio": a["us"] / a["tme_us"] if a["tme_us"] > 0 else 0.0,
        })
    return rows


def render(rows: List[Dict[str, Any]], chip: str = "") -> str:
    """Fixed-width text table of ``table_rows`` output."""
    head = f"measured vs TME-predicted (chip model: {chip})" if chip else \
        "measured vs TME-predicted"
    lines = [head,
             f"{'kind':<14} {'route':<8} {'calls':>6} {'mean_us':>12} "
             f"{'tme_us':>12} {'ratio':>10}"]
    for r in rows:
        ratio = f"{r['ratio']:.1f}x" if r["ratio"] else "-"
        tme_us = f"{r['tme_us']:.3f}" if r["tme_us"] else "-"
        lines.append(f"{r['kind']:<14} {r['route'] or '-':<8} "
                     f"{r['calls']:>6d} {r['mean_us']:>12.2f} "
                     f"{tme_us:>12} {ratio:>10}")
    return "\n".join(lines)


def _builtin_sweep() -> None:
    """Tiny workload touching every dispatch kind + the reductions, so a bare
    ``python -m repro.obs.report`` demonstrates the instrument end to end."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import compensated, dispatch, ozaki2

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 64)))
    b = jnp.asarray(rng.standard_normal((64, 64)))
    v = jnp.asarray(rng.standard_normal((64, 4)))
    u = jnp.asarray(rng.standard_normal((8, 8, 8)))
    c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
    # r = 7 plan: the default-plan interpreted SpMV costs minutes of XLA-CPU
    # compile (ROADMAP); the bounded plan keeps the demo in seconds.
    plan_r7 = ozaki2.make_plan(4, payload_bits=24, margin_bits=4)
    val = jnp.asarray(rng.standard_normal((32, 4)))
    col = jnp.asarray(rng.integers(0, 32, (32, 4)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(32))
    q = jnp.asarray(rng.standard_normal((16, 8)))
    kq = jnp.asarray(rng.standard_normal((16, 8)))
    vq = jnp.asarray(rng.standard_normal((16, 8)))
    causal = jnp.tril(jnp.ones((16, 16), jnp.int8))
    for mode in ("xla", "pallas"):
        dispatch.matmul(a, b, mode=mode)
        dispatch.matmul(a, v, mode=mode)
        dispatch.stencil7(u, c, bx=4, mode=mode)
        dispatch.spmv(val, col, x, plan=plan_r7, br=8, mode=mode)
        dispatch.attention(q, kq, vq, mask=causal, mode=mode)
    compensated.compensated_dot(jnp.asarray(rng.standard_normal(4096)),
                                jnp.asarray(rng.standard_normal(4096)))


def main(argv=None) -> int:
    """CLI entry: report a saved snapshot, or sweep-and-report (see module)."""
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("snapshot", nargs="?", default=None,
                        help="telemetry snapshot JSON (from "
                             "telemetry.write_json); omitted = run a small "
                             "built-in sweep and report it")
    parser.add_argument("--json", action="store_true",
                        help="emit the table as JSON rows instead of text")
    args = parser.parse_args(argv)

    if args.snapshot is None:
        # Standalone CLI: the emulation kernels assume f64 operands (the
        # benchmark harness and test conftest both enable x64 before jax
        # initialises; this entry point must too).
        import jax

        jax.config.update("jax_enable_x64", True)

    if args.snapshot is not None:
        with open(args.snapshot) as fh:
            snap = json.load(fh)
    else:
        telemetry.reset()
        with telemetry.telemetry_scope("trace"):
            _builtin_sweep()
        snap = telemetry.snapshot()

    rows = table_rows(snap)
    if args.json:
        json.dump(rows, sys.stdout, indent=2)
        print()
    else:
        print(render(rows, chip=snap.get("chip", "")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
