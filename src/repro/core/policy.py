"""Precision policies — the paper's §8 recommendation made concrete.

"Ozaki-style emulation should be integrated systematically into the standard HPC
libraries and exposed to applications behind precision-policy interfaces."  Every
weight matmul in ``repro.models`` goes through ``Policy.dot``; flipping the policy
swaps the arithmetic between the native MXU paths and the Ozaki emulation paths with
no model-code changes.

Policies:
  bf16        — native mixed precision (bf16 operands, f32 accumulation).  Production
                default; what the dry-run/roofline baselines use.
  fp32        — f32 operands and accumulation.
  fp64        — XLA software float64 (the oracle; CPU tests only — TPU has no FP64
                unit, which is exactly the paper's point).
  ozaki2_int8 — Ozaki Scheme II on the int8 MXU path (CRT, r moduli).
  ozaki2_fp8  — Ozaki Scheme II on the FP8 substrate (§2.4 quantisation trick).
  ozaki1_int8 — Ozaki Scheme I mantissa slicing (S² GEMMs) — the paper's baseline.

Emulated paths carry a custom VJP: the gradient of an FP64-accurate matmul is the
FP64-accurate matmul of the gradients, so emulated training is end-to-end exact (see
examples/fp64_exact_training.py).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import backend, dispatch, ozaki1, ozaki2

POLICIES = ("bf16", "fp32", "fp64", "ozaki2_int8", "ozaki2_fp8", "ozaki1_int8")


def _flatten_dot(fn):
    """Lift a 2D (m,k)x(k,n) matmul to (..., k) x (k, n)."""
    @functools.wraps(fn)
    def wrapped(x, w, *a, **kw):
        lead = x.shape[:-1]
        out = fn(x.reshape((-1, x.shape[-1])), w, *a, **kw)
        return out.reshape(lead + (w.shape[-1],))
    return wrapped


# --- differentiable emulated matmul ----------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ozaki2_dot(a: jax.Array, b: jax.Array, plan: ozaki2.Plan) -> jax.Array:
    return dispatch.matmul(a, b, plan=plan)


def _ozaki2_dot_fwd(a, b, plan):
    return ozaki2_dot(a, b, plan), (a, b)


def _ozaki2_dot_bwd(plan, res, g):
    a, b = res
    # Gradients of C = A B under the same emulated arithmetic:
    #   dA = g B^T, dB = A^T g — contraction length changes, so re-plan
    #   (cache-resolved: the bwd plans are the fwd plans of other layers).
    plan_da = dispatch.get_plan(g.shape[-1], plan.payload_bits,
                                substrate=plan.substrate)
    plan_db = dispatch.get_plan(a.shape[0], plan.payload_bits,
                                substrate=plan.substrate)
    da = dispatch.matmul(g, b.T, plan=plan_da)
    db = dispatch.matmul(a.T, g, plan=plan_db)
    return da.astype(a.dtype), db.astype(b.dtype)


ozaki2_dot.defvjp(_ozaki2_dot_fwd, _ozaki2_dot_bwd)


@jax.custom_vjp
def ozaki1_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return ozaki1.emulated_matmul(a, b, out_dtype=backend.working_float())


def _ozaki1_dot_fwd(a, b):
    return ozaki1_dot(a, b), (a, b)


def _ozaki1_dot_bwd(res, g):
    a, b = res
    da = ozaki1.emulated_matmul(g, b.T, out_dtype=backend.working_float())
    db = ozaki1.emulated_matmul(a.T, g, out_dtype=backend.working_float())
    return da.astype(a.dtype), db.astype(b.dtype)


ozaki1_dot.defvjp(_ozaki1_dot_fwd, _ozaki1_dot_bwd)


# --- the policy object ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Policy:
    """Dispatches matmuls to a numeric path.  Hashable — safe as a static arg."""

    name: str = "bf16"
    payload_bits: int = 53

    def __post_init__(self):
        if self.name not in POLICIES:
            raise ValueError(f"unknown policy {self.name!r}; choose from {POLICIES}")

    @property
    def is_emulated(self) -> bool:
        return self.name.startswith("ozaki")

    def dot(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """y[..., n] = x[..., k] @ w[k, n] under this policy.

        Output dtype matches x's dtype for the native paths (accumulation in f32);
        emulated paths compute at working-f64 and cast back to x.dtype.
        """
        if self.name == "bf16":
            return jax.lax.dot_general(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(x.dtype)
        if self.name == "fp32":
            return jax.lax.dot_general(
                x.astype(jnp.float32), w.astype(jnp.float32),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(x.dtype)
        if self.name == "fp64":
            f64 = backend.working_float()
            return jnp.dot(x.astype(f64), w.astype(f64)).astype(x.dtype)
        if self.name in ("ozaki2_int8", "ozaki2_fp8"):
            substrate = self.name.split("_")[1]
            plan = dispatch.get_plan(x.shape[-1], self.payload_bits,
                                     substrate=substrate)
            f64 = backend.working_float()
            out = _flatten_dot(ozaki2_dot)(x.astype(f64), w.astype(f64), plan)
            return out.astype(x.dtype)
        if self.name == "ozaki1_int8":
            f64 = backend.working_float()
            out = _flatten_dot(ozaki1_dot)(x.astype(f64), w.astype(f64))
            return out.astype(x.dtype)
        raise AssertionError(self.name)

    def matmul_flops_multiplier(self) -> int:
        """TME α for this policy (1 for native paths) — used by the roofline tooling."""
        if self.name in ("bf16", "fp32", "fp64"):
            return 1
        if self.name == "ozaki2_int8":
            return 16          # r at k~4096, p=53
        if self.name == "ozaki2_fp8":
            return 48          # 3r
        if self.name == "ozaki1_int8":
            return 64          # S² at S=8
        raise AssertionError(self.name)


DEFAULT_POLICY = Policy("bf16")
