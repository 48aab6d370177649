"""Compile rehearsal for one TPU v5e chip: the dispatch kinds at real widths.

Each test compiles one route of one kind for a *described* v5e chip (no chip
attached) and checks that the TPU compiler accepts it — what interpret-mode
tests cannot show: Mosaic's tiling rules, its lack of float64, VMEM limits,
and the f64 ops that XLA:TPU lowers.  The pallas compiles must contain the
Mosaic kernel (``tpu_custom_call``).

The program picks its TPU branch from the backend it runs on, and here the
backend is the CPU.  So the ``tpu`` fixture steers the one backend query the
program's branches share (``repro.core.backend.name``) for a test's duration.
The topology is described in a module fixture, never at import: the TPU
library admits one process at a time, and xdist workers import every test
file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import spectral
from repro.core import backend, compensated, dispatch
from repro.hpc import jacobi
from repro.kernels import ozaki_spmv

F64, I32 = jnp.float64, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the TPU library logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu(monkeypatch, one_chip, no_persistent_cache):
    """Make the dispatch layer take the branch it takes on a TPU backend."""
    monkeypatch.setattr(backend, "name", lambda: "tpu")
    return lambda shape, dtype=F64: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one_chip)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_gemm_compiles(tpu, route):
    txt = _compile_text(lambda a, b: dispatch.matmul(a, b, mode=route),
                        tpu((1024, 1024)), tpu((1024, 1024)))
    assert ("tpu_custom_call" in txt) == (route == "pallas")


def test_gemv_compiles(tpu):
    txt = _compile_text(lambda a, x: dispatch.matmul(a, x, mode="pallas"),
                        tpu((8192, 8192)), tpu((8192, 8)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("banded", [False, True])
def test_spmv_bell_compiles(tpu, banded):
    """The 7-point operator at 64^3: x gathered (in the kernel from VMEM, or
    in XLA, as ``x_in_vmem`` decides for its length), or (banded) laid out
    by static shifts of x with no gather in the program."""
    g = 64
    offsets = (0, -g * g, g * g, -g, g, -1, 1) if banded else None
    n = g ** 3
    txt = _compile_text(
        lambda v, c, x: dispatch.spmv(v, c, x, mode="pallas", offsets=offsets),
        tpu((n, 7)), tpu((n, 7), I32), tpu((n,)))
    assert "tpu_custom_call" in txt
    no_xla_gather = banded or ozaki_spmv.x_in_vmem(n, 7, 128)
    assert (re.search(r"\sgather\(", txt) is None) == no_xla_gather


def test_spmv_bell_compiles_at_npb_class_b(tpu):
    """NPB CG class B's operator (75000 rows, bw 404, plan r = 16): the
    general path, x resident in VMEM and gathered inside the Mosaic kernel,
    which keeps its name; no XLA gather in the program."""
    m, bw = 75000, 404
    txt = _compile_text(lambda v, c, x: dispatch.spmv(v, c, x, mode="pallas"),
                        tpu((m, bw)), tpu((m, bw), I32), tpu((m,)))
    assert re.search(r"%spmv_bell(\.\d+)? = \S+ custom-call\(", txt) is not None
    assert "tpu_custom_call" in txt
    assert re.search(r"\sgather\(", txt) is None


def test_stencil7_compiles(tpu):
    c = jacobi.laplacian_coeffs()
    txt = _compile_text(lambda u: dispatch.stencil7(u, c, mode="pallas"),
                        tpu((256, 256, 256)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("S", [2048, 1])
def test_attention_compiles(tpu, S):
    txt = _compile_text(
        lambda q, k, v, m: dispatch.attention(q, k, v, mask=m, mode="pallas"),
        tpu((8, S, 128)), tpu((8, 2048, 128)), tpu((8, 2048, 128)),
        tpu((S, 2048), jnp.int8))
    assert "tpu_custom_call" in txt


def test_fft_compiles(tpu):
    """XLA:TPU compiles no complex128 op; the transform runs on real and
    imaginary parts, every GEMM on the fused kernel."""
    txt = _compile_text(lambda a, b: spectral.fft_parts(a, b, mode="pallas"),
                        tpu((8, 65536)), tpu((8, 65536)))
    assert "tpu_custom_call" in txt


def test_solver_reductions_compile(tpu):
    """CG and Jacobi reduce on the device with the compensated dot and norm;
    their float64 scaling must lower on XLA:TPU too."""
    n = 64 ** 3
    _compile_text(compensated.compensated_norm, tpu((n,)))
    _compile_text(compensated.compensated_dot, tpu((n,)), tpu((n,)))
