"""The one place the program asks which backend it runs on.

Every branch that differs between a TPU and the other backends keys on
``name()``: the dispatch layer's auto routes and Pallas flavour (Mosaic or
the interpreter), and the float64 arithmetic that has to change on a TPU.
``working_float`` is the one place the program picks the float that the
emulation computes in.

XLA:TPU emulates float64 as a pair of float32 values.  Measured on a TPU v5e
(phase 0 of ``chip_smoke.py``):

  * storage keeps 53 significand bits: integers up to 2^53 - 1 and 1 + 2^-52
    round-trip exactly;
  * the exponent range is float32's, 2^-149 to 2^127: 2^-1022 comes back 0
    and 2^1000 comes back inf;
  * one arithmetic op errs by up to 4.58e-14 relative (sqrt; div 3.88e-14,
    add 5.47e-15), so the device's unit roundoff is about 2^-44.3, not 2^-53;
  * ``exp`` over a one-element float64 array errs at float32 level (1.7e-8);
    over (128,), (1, 128), (2, 128) or (8, 128) it errs by 4e-15 to 9e-15;
  * there are no 64-bit bitcasts, so ``jnp.ldexp`` and bit-field tricks on
    float64 do not compile (``splitting.ldexp`` replaces the former).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def name() -> str:
    """Platform of the default backend ("tpu", "cpu", "gpu")."""
    return jax.default_backend()


def float64_is_f32_pair() -> bool:
    """Whether float64 on this backend is XLA's float32-pair emulation."""
    return name() == "tpu"


def working_float():
    """The float the emulation scales, reconstructs and returns in: float64
    when x64 is enabled, else float32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
