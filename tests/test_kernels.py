"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Every fused Pallas kernel (interpret=True on this CPU container; Mosaic on TPU) is
checked two ways:
  1. accuracy vs the float64 oracle (§2.5 error band),
  2. BIT-EXACT equality of the f64 output mode against the unfused XLA
     implementation (repro.core.ozaki2) — this pins every integer step.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, ozaki2, splitting
from repro.kernels import ozaki_gemm, ozaki_gemv, ref

U64 = 2.0 ** -53
RNG = np.random.default_rng(123)


def _gemm_err(c, a, b):
    denom = np.abs(np.asarray(a)) @ np.abs(np.asarray(b)) + 1e-300
    return np.max(np.abs(np.asarray(c) - np.asarray(ref.gemm_f64(a, b))) / denom)


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn,blocks", [
    ((16, 32, 16), (16, 16, 32)),
    ((40, 70, 24), (16, 8, 32)),       # ragged: padding path
    ((128, 256, 64), (64, 32, 128)),   # multi-step K accumulation
    ((8, 8, 8), (8, 8, 8)),            # single block
])
@pytest.mark.parametrize("out_rep", ["f64", "digits"])
def test_gemm_accuracy_sweep(mkn, blocks, out_rep):
    m, k, n = mkn
    bm, bn, bk = blocks
    a = jnp.asarray(RNG.standard_normal((m, k)))
    b = jnp.asarray(RNG.standard_normal((k, n)))
    c = ozaki_gemm.gemm(a, b, dispatch.get_plan(k), out_rep=out_rep,
                        bm=bm, bn=bn, bk=bk)
    assert _gemm_err(c, a, b) <= 16 * U64


def test_gemm_ds_mode_precision():
    a = jnp.asarray(RNG.standard_normal((32, 64)))
    b = jnp.asarray(RNG.standard_normal((64, 32)))
    c = ozaki_gemm.gemm(a, b, dispatch.get_plan(64), out_rep="ds",
                        bm=16, bn=16, bk=32)
    err = _gemm_err(c, a, b)
    assert err <= 2.0 ** -44  # double-single carries ~45-48 bits
    assert err > 2.0 ** -60   # ...but is not full f64 (sanity on the mode split)


def _xla_ozaki2(a, b, plan, out_rep):
    """The unfused XLA pipeline's answer in the kernel's output representation."""
    if out_rep == "f64":
        return ozaki2.emulated_matmul(a, b, plan)
    ares, sa = ozaki2.decompose(a, plan, scale_axis=-1)
    bres, sb = ozaki2.decompose(b, plan, scale_axis=0)
    digits = ozaki2.garner_digits(list(ozaki2.modular_matmul(ares, bres, plan)),
                                  plan)
    hi, lo = ozaki2.digits_to_ds(digits, plan)
    return splitting.apply_unscale(hi.astype(jnp.float64) + lo.astype(jnp.float64),
                                   sa, sb)


@pytest.mark.parametrize("mkn,blocks,interval", [
    ((24, 48, 16), (8, 8, 16), None),      # 3 x 2 x 3 grid
    ((100, 100, 50), (64, 16, 32), None),  # 2 x 4 x 4 grid, ragged on every axis,
                                           # Garner in two strips of rows
    ((100, 100, 50), (64, 16, 32), 3),     # the accumulator reduced mid-loop
])
@pytest.mark.parametrize("out_rep", ["f64", "ds"])
def test_gemm_kernel_bitexact_vs_xla_ozaki2(mkn, blocks, interval, out_rep,
                                            monkeypatch):
    m, k, n = mkn
    bm, bn, bk = blocks
    a = jnp.asarray(RNG.standard_normal((m, k)))
    b = jnp.asarray(RNG.standard_normal((k, n)))
    plan = ozaki2.make_plan(k)
    if interval is not None:
        # K would have to pass ~131k for the reduction to fall mid-loop.
        monkeypatch.setattr(ozaki_gemm, "reduce_interval", lambda bk, ms: interval)
    ozaki_gemm.gemm_hilo.clear_cache()
    try:
        c_kernel = ozaki_gemm.gemm(a, b, plan=plan, out_rep=out_rep,
                                   bm=bm, bn=bn, bk=bk)
    finally:
        ozaki_gemm.gemm_hilo.clear_cache()
    c_xla = _xla_ozaki2(a, b, plan, out_rep)
    np.testing.assert_array_equal(np.asarray(c_kernel), np.asarray(c_xla))


@pytest.mark.parametrize("bk", [16, 256, 512])
def test_gemm_reduce_interval_cannot_overflow(bk):
    moduli = dispatch.get_plan(8192).moduli
    s = ozaki_gemm.reduce_interval(bk, moduli)
    h = max(m // 2 for m in moduli)        # largest |balanced residue|
    assert s >= 1
    assert s * bk * h * h + h < 2 ** 31
    # At the dgemm cell's shape the accumulator is reduced once, at the end.
    _, _, tuned_bk = dispatch.choose_blocks(8192, 8192, 8192)
    assert ozaki_gemm.reduce_interval(tuned_bk, moduli) >= 8192 // tuned_bk
    with pytest.raises(ValueError, match="overflow"):
        ozaki_gemm.reduce_interval(1 << 17, moduli)


def test_gemm_f32_inputs():
    a = jnp.asarray(RNG.standard_normal((16, 32)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((32, 16)), jnp.float32)
    plan = ozaki2.make_plan(32, payload_bits=24)
    c = ozaki_gemm.gemm(a, b, plan=plan, bm=16, bn=16, bk=32)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    denom = np.abs(np.asarray(a, np.float64)) @ np.abs(np.asarray(b, np.float64))
    assert np.max(np.abs(np.asarray(c) - want) / denom) <= 2.0 ** -22


# ---------------------------------------------------------------------------
# Batched GEMV (Algorithm 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mnb", [(64, 96, 8), (33, 70, 2), (128, 64, 4)])
@pytest.mark.parametrize("out_rep", ["f64", "digits"])
def test_gemv_accuracy_sweep(mnb, out_rep):
    m, n, bsz = mnb
    a = jnp.asarray(RNG.standard_normal((m, n)))
    x = jnp.asarray(RNG.standard_normal((n, bsz)))
    y = ozaki_gemv.gemv(a, x, dispatch.get_plan(n), out_rep=out_rep,
                        bm=16, bk=32)
    denom = np.abs(np.asarray(a)) @ np.abs(np.asarray(x)) + 1e-300
    err = np.max(np.abs(np.asarray(y) - np.asarray(ref.gemv_f64(a, x))) / denom)
    assert err <= 16 * U64


def test_gemv_matches_gemm_kernel():
    a = jnp.asarray(RNG.standard_normal((32, 64)))
    x = jnp.asarray(RNG.standard_normal((64, 8)))
    plan = ozaki2.make_plan(64)
    y1 = ozaki_gemv.gemv(a, x, plan=plan, bm=16, bk=32)
    y2 = ozaki_gemm.gemm(a, x, plan=plan, bm=16, bn=8, bk=32)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


# ---------------------------------------------------------------------------
# 7-point stencil (Algorithm 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bx", [
    ((12, 10, 20), 4),
    ((8, 8, 8), 8),      # single slab
    ((6, 7, 13), 4),     # ragged x: padding path
])
@pytest.mark.parametrize("out_rep", ["f64", "digits"])
def test_stencil_accuracy_sweep(shape, bx, out_rep):
    u = jnp.asarray(RNG.standard_normal(shape))
    c = jnp.asarray(np.array([6.0, -1.0, -1.0, -1.0, -1.0, -1.0, -1.0]))
    v = dispatch.stencil7(u, c, out_rep=out_rep, bx=bx)
    want = np.asarray(ref.stencil7_f64(u, c))
    scale = 7 * np.max(np.abs(np.asarray(u))) * np.max(np.abs(np.asarray(c)))
    assert np.max(np.abs(np.asarray(v) - want)) <= 8 * U64 * scale
    assert v.shape == shape


def test_stencil_boundary_zero_halo():
    """Points on the global boundary must see a zero halo, not wraparound."""
    u = jnp.asarray(np.ones((4, 4, 8)))
    c = jnp.asarray(np.array([0.0, 0.0, 0.0, 0.0, 0.0, 1.0, 0.0]))  # pure -z shift
    v = np.asarray(dispatch.stencil7(u, c, bx=4))
    assert np.all(v[:, :, 0] == 0.0)   # first plane has no -z neighbour
    assert np.all(v[:, :, 1:] == 1.0)


def test_stencil_anisotropic_coeffs():
    u = jnp.asarray(RNG.standard_normal((8, 8, 8)))
    c = jnp.asarray(RNG.standard_normal(7))
    v = np.asarray(dispatch.stencil7(u, c, bx=4))
    want = np.asarray(ref.stencil7_f64(u, c))
    scale = float(7 * jnp.max(jnp.abs(u)) * jnp.max(jnp.abs(c)))
    assert np.max(np.abs(v - want)) <= 8 * U64 * scale


# ---------------------------------------------------------------------------
# Blocked-ELL SpMV (Algorithm 3)
# ---------------------------------------------------------------------------

def _random_bell(m, n, bw, zero_frac=0.2):
    col = RNG.integers(0, n, (m, bw)).astype(np.int32)
    val = RNG.standard_normal((m, bw))
    val[RNG.random((m, bw)) < zero_frac] = 0.0  # structural zeros (padding)
    return jnp.asarray(val), jnp.asarray(col), jnp.asarray(RNG.standard_normal(n))


@pytest.mark.parametrize("mnbw", [(50, 64, 8), (128, 32, 16), (17, 100, 4)])
@pytest.mark.parametrize("out_rep", ["f64", "digits"])
def test_spmv_accuracy_sweep(mnbw, out_rep):
    # mode="xla" pins the arithmetic to the bit-identical reference route:
    # accuracy is route-independent, and under the CI REPRO_DISPATCH=pallas
    # leg the default-plan interpreter would pay minutes of XLA-CPU compile.
    m, n, bw = mnbw
    val, col, x = _random_bell(m, n, bw)
    y = dispatch.spmv(val, col, x, out_rep=out_rep, br=16, mode="xla")
    want = np.asarray(ref.spmv_bell_f64(val, col, x))
    denom = (np.abs(np.asarray(val)).sum(-1) * np.max(np.abs(np.asarray(x)))
             + 1e-300)
    assert np.max(np.abs(np.asarray(y) - want) / denom) <= 16 * U64


def test_spmv_laplacian_1d():
    """A real PDE matrix: 1-D Laplacian in ELL form, y = A x exact vs dense."""
    n = 96
    dense = (np.diag(2.0 * np.ones(n)) - np.diag(np.ones(n - 1), 1)
             - np.diag(np.ones(n - 1), -1))
    col = np.zeros((n, 4), np.int32)
    val = np.zeros((n, 4))
    for i in range(n):
        nz = [(j, dense[i, j]) for j in range(n) if dense[i, j] != 0]
        for s, (j, v) in enumerate(nz):
            col[i, s], val[i, s] = j, v
    x = RNG.standard_normal(n)
    y = np.asarray(dispatch.spmv(jnp.asarray(val), jnp.asarray(col),
                                 jnp.asarray(x), br=32, mode="xla"))
    np.testing.assert_allclose(y, dense @ x, rtol=0, atol=4 * U64 * 4 * np.abs(x).max())


@pytest.mark.slow  # interpret-mode SpMV via pallas route: XLA-CPU compile cost
def test_spmv_routes_bit_identical_pallas_interpreter():
    """The xla route (jnp reference, the CPU default) matches the pallas
    route bit-for-bit through the dispatch seam: same scaling, residues,
    contraction, and Garner digits — routing by ``mode=``, never
    ``interpret=``.

    A 24-bit-payload plan (r = 7) keeps the interpreted Garner graph
    compileable in seconds; the default r = 15 plan's interpreted gather
    graph costs 10+ minutes of XLA-CPU compile (ROADMAP) regardless of
    problem size, so NO CPU lane covers it — on-TPU runs of the same tests
    exercise the compiled Mosaic kernel at the default plan.  Bit-identity is
    plan-independent (the decompose prologue is shared code and every integer
    step is exact), so this plan pins the whole path; ragged M exercises the
    row-padding of the fused kernel.
    """
    from repro.core import ozaki2
    plan = ozaki2.make_plan(4, payload_bits=24)
    val, col, x = _random_bell(27, 32, 4)    # 27 % br != 0: padding path
    for rep in ("f64", "digits"):
        y_ref = np.asarray(dispatch.spmv(val, col, x, plan=plan,
                                         out_rep=rep, mode="xla"))
        y_pal = np.asarray(dispatch.spmv(val, col, x, plan=plan, br=8,
                                         out_rep=rep, mode="pallas"))
        np.testing.assert_array_equal(y_ref, y_pal)


def _last_chunk_bell(m, n, bw, br):
    """Every column in x's last, partial chunk of br entries."""
    val, _, x = _random_bell(m, n, bw)
    col = RNG.integers(n - n % br, n, (m, bw)).astype(np.int32)
    return val, jnp.asarray(col), x


@pytest.mark.parametrize("operator", [
    lambda: _random_bell(27, 64, 8),         # ragged M: padded rows
    lambda: _random_bell(32, 37, 8),         # n not a multiple of br
    lambda: _random_bell(24, 40, 13),        # bw not a multiple of 8
    lambda: _last_chunk_bell(16, 45, 5, 8),  # columns in the last, partial chunk
], ids=["ragged-m", "ragged-n", "bw-13", "last-chunk"])
def test_spmv_vmem_gather_bit_identical_to_xla(operator):
    """The pallas route with x resident in VMEM and gathered in the kernel
    matches the xla route's gather bit for bit (24-bit plan, interpreted)."""
    from repro.kernels import ozaki_spmv
    plan = ozaki2.make_plan(13, payload_bits=24)
    val, col, x = operator()
    assert ozaki_spmv.x_in_vmem(x.shape[0], val.shape[1], 8)
    for rep in ("f64", "digits"):
        y_ref = np.asarray(dispatch.spmv(val, col, x, plan=plan,
                                         out_rep=rep, mode="xla"))
        y_pal = np.asarray(dispatch.spmv(val, col, x, plan=plan, br=8,
                                         out_rep=rep, mode="pallas"))
        np.testing.assert_array_equal(y_ref, y_pal)


# ---------------------------------------------------------------------------
# Layering
# ---------------------------------------------------------------------------

def test_kernels_import_nothing_from_dispatch_or_policy():
    """The kernel layer sits under the dispatch seam: no module under
    ``repro.kernels`` imports ``repro.core.dispatch`` or ``repro.core.policy``."""
    import ast
    import pathlib

    import repro.kernels

    upward = {"repro.core.dispatch", "repro.core.policy"}
    offenders = []
    for path in sorted(pathlib.Path(repro.kernels.__file__).parent.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = {alias.name for alias in node.names}
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = {node.module} | {f"{node.module}.{alias.name}"
                                         for alias in node.names}
            else:
                continue
            offenders += [(path.name, n) for n in sorted(names & upward)]
    assert offenders == []
