"""Dispatch layer: plan caching, XLA/Pallas routing, padding, mode override."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, ozaki2
from repro.core.policy import Policy

U64 = 2.0 ** -53
RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

def test_get_plan_matches_make_plan():
    for k, p, sub in [(64, 53, "int8"), (300, 53, "fp8"), (64, 24, "int8")]:
        assert dispatch.get_plan(k, p, sub) == ozaki2.make_plan(k, p, substrate=sub)


def test_get_plan_is_cached_identity():
    a = dispatch.get_plan(96)
    b = dispatch.get_plan(96)
    assert a is b
    assert a.garner is b.garner  # Garner constants primed once, shared


def test_policy_dot_hot_path_skips_make_plan(monkeypatch):
    """After the cache is warm, Policy.dot never re-enters make_plan."""
    x = jnp.asarray(RNG.standard_normal((4, 48)))
    w = jnp.asarray(RNG.standard_normal((48, 4)))
    Policy("ozaki2_int8").dot(x, w)  # warm the (k=48, p=53, int8) entry

    calls = {"n": 0}
    real = ozaki2.make_plan

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(ozaki2, "make_plan", counting)
    for _ in range(3):
        Policy("ozaki2_int8").dot(x, w)
    assert calls["n"] == 0


def test_plan_cache_distinguishes_substrate_and_payload():
    assert dispatch.get_plan(64, 53, "int8") is not dispatch.get_plan(64, 53, "fp8")
    assert dispatch.get_plan(64, 53, "int8") is not dispatch.get_plan(64, 24, "int8")


# ---------------------------------------------------------------------------
# Mode resolution / env override
# ---------------------------------------------------------------------------

def test_env_var_selects_mode(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    assert dispatch.get_mode() == "pallas"
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    assert dispatch.get_mode() == "xla"
    monkeypatch.delenv(dispatch.ENV_VAR)
    assert dispatch.get_mode() == "auto"


def test_invalid_mode_rejected(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "cuda")
    with pytest.raises(ValueError):
        dispatch.get_mode()
    with pytest.raises(ValueError):
        dispatch.set_mode("fast")


def test_mode_scope_overrides_env_and_restores(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    with dispatch.mode_scope("pallas"):
        assert dispatch.get_mode() == "pallas"
        with dispatch.mode_scope(None):     # None inherits
            assert dispatch.get_mode() == "pallas"
    assert dispatch.get_mode() == "xla"


def test_choose_route():
    int8 = dispatch.get_plan(64, substrate="int8")
    fp8 = dispatch.get_plan(64, substrate="fp8")
    assert dispatch.choose_route(int8, mode="xla") == "xla"
    assert dispatch.choose_route(int8, mode="pallas") == "pallas"
    # fp8 has no fused kernel: always the XLA reference path
    assert dispatch.choose_route(fp8, mode="pallas") == "xla"
    # auto on this CPU container avoids interpret-mode Pallas
    if jax.default_backend() != "tpu":
        assert dispatch.choose_route(int8, mode="auto") == "xla"


def test_choose_route_is_kind_aware():
    """Every fused-kernel kind resolves through the same seam: explicit modes
    win, fp8 falls back, and auto follows the per-kind backend table."""
    int8 = dispatch.get_plan(64, substrate="int8")
    fp8 = dispatch.get_plan(64, substrate="fp8")
    for kind in dispatch.KINDS:
        assert dispatch.choose_route(int8, kind, "xla") == "xla"
        assert dispatch.choose_route(int8, kind, "pallas") == "pallas"
        assert dispatch.choose_route(fp8, kind, "pallas") == "xla"
        table = dispatch.AUTO_ROUTE[kind]
        want = table.get(jax.default_backend(), table["default"])
        assert dispatch.choose_route(int8, kind, "auto") == want
    with pytest.raises(ValueError):
        dispatch.choose_route(int8, "conv3x3")
    with pytest.raises(ValueError):
        dispatch.pallas_interpret("conv3x3")


def test_matmul_kind_split_matches_gemv_threshold():
    assert dispatch._matmul_kind(1) == "gemv"
    assert dispatch._matmul_kind(dispatch.GEMV_MAX_B) == "gemv"
    assert dispatch._matmul_kind(dispatch.GEMV_MAX_B + 1) == "gemm"


# ---------------------------------------------------------------------------
# Routing correctness
# ---------------------------------------------------------------------------

def test_pallas_route_bit_identical_evenly_tiled(monkeypatch):
    """REPRO_DISPATCH=pallas on an evenly-tiled f64 matmul == XLA bit-for-bit."""
    x = jnp.asarray(RNG.standard_normal((128, 256)))
    w = jnp.asarray(RNG.standard_normal((256, 128)))
    pol = Policy("ozaki2_int8")
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    y_xla = np.asarray(pol.dot(x, w))
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    y_pal = np.asarray(pol.dot(x, w))
    np.testing.assert_array_equal(y_xla, y_pal)


@pytest.mark.parametrize("mkn", [(40, 70, 24), (8, 48, 8), (129, 257, 100)])
def test_pallas_route_padding_ragged_shapes(mkn):
    """Ragged shapes pad to MXU blocks; results stay bit-identical to XLA."""
    m, k, n = mkn
    a = jnp.asarray(RNG.standard_normal((m, k)))
    b = jnp.asarray(RNG.standard_normal((k, n)))
    y_xla = np.asarray(dispatch.matmul(a, b, mode="xla"))
    y_pal = np.asarray(dispatch.matmul(a, b, mode="pallas"))
    assert y_pal.shape == (m, n)
    np.testing.assert_array_equal(y_xla, y_pal)
    denom = np.abs(np.asarray(a)) @ np.abs(np.asarray(b)) + 1e-300
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert np.max(np.abs(y_pal - want) / denom) <= 16 * U64


@pytest.mark.parametrize("n", [1, 8, dispatch.GEMV_MAX_B, dispatch.GEMV_MAX_B + 1])
def test_pallas_narrow_rhs_routes_via_gemv(n):
    """n <= GEMV_MAX_B uses the fused GEMV kernel; both sides bit-match XLA."""
    a = jnp.asarray(RNG.standard_normal((40, 64)))
    b = jnp.asarray(RNG.standard_normal((64, n)))
    y_xla = np.asarray(dispatch.matmul(a, b, mode="xla"))
    y_pal = np.asarray(dispatch.matmul(a, b, mode="pallas"))
    np.testing.assert_array_equal(y_xla, y_pal)


def test_pad_operands_blocks_divide_padded_shapes():
    a = jnp.zeros((40, 70))
    b = jnp.zeros((70, 24))
    ap, bp, (bm, bn, bk) = dispatch.pad_operands(a, b)
    assert ap.shape[0] % bm == 0 and ap.shape[1] % bk == 0
    assert bp.shape[0] % bk == 0 and bp.shape[1] % bn == 0
    assert ap.shape[0] % dispatch.SUBLANE == 0
    assert bp.shape[1] % dispatch.LANE == 0


def test_dispatch_dot_batched_leading_dims():
    x = jnp.asarray(RNG.standard_normal((3, 5, 32)))
    w = jnp.asarray(RNG.standard_normal((32, 16)))
    y = dispatch.dot(x, w, mode="pallas")
    want = np.asarray(x).reshape(-1, 32) @ np.asarray(w)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want, rtol=1e-12)


def test_policy_grads_under_pallas_route(monkeypatch):
    """The custom VJP stays exact when the forward/backward route is fused."""
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    x = jnp.asarray(RNG.standard_normal((8, 32)))
    w = jnp.asarray(RNG.standard_normal((32, 8)))

    def loss(pol, a, b):
        return jnp.sum(pol.dot(a, b) ** 2)

    gx64, gw64 = jax.grad(lambda a, b: loss(Policy("fp64"), a, b), (0, 1))(x, w)
    gxe, gwe = jax.grad(
        lambda a, b: loss(Policy("ozaki2_int8"), a, b), (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gxe), np.asarray(gx64), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(gwe), np.asarray(gw64), rtol=1e-12)


def test_fp8_policy_ignores_pallas_request(monkeypatch):
    """ozaki2_fp8 has no fused kernel; pallas mode falls back and stays exact."""
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    x = jnp.asarray(RNG.standard_normal((8, 64)))
    w = jnp.asarray(RNG.standard_normal((64, 8)))
    got = np.asarray(Policy("ozaki2_fp8").dot(x, w))
    want = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    denom = np.abs(np.asarray(x)) @ np.abs(np.asarray(w))
    assert np.max(np.abs(got - want) / denom) <= 16 * U64


def test_cg_dense_dispatch_converges():
    from repro.hpc import spmv_formats
    from repro.hpc.cg import cg_solve_dense

    dense = jnp.asarray(spmv_formats.laplacian_2d(6, 6))
    b = jnp.asarray(RNG.standard_normal(36))
    res = cg_solve_dense(dense, b, tol=1e-10)
    assert res.converged
    np.testing.assert_allclose(np.asarray(dense) @ np.asarray(res.x),
                               np.asarray(b), atol=1e-8)


# ---------------------------------------------------------------------------
# SpMV / stencil on the seam (mode flipping end-to-end)
# ---------------------------------------------------------------------------

def _spy_spmv_routes(monkeypatch):
    """Replace both SpMV routes with recorders (the pallas interpreter costs
    minutes of XLA-CPU compile, so the spy must intercept, not wrap)."""
    from repro.kernels import ozaki_spmv

    calls = []
    real_ref = ozaki_spmv.spmv_bell_ref

    def ref_spy(*a, **kw):
        calls.append("xla")
        return real_ref(*a, **kw)

    def pallas_spy(a_val, a_col, x, plan, out_rep="f64", br=128,
                   interpret=True, offsets=None):
        calls.append("pallas")
        assert interpret == dispatch.pallas_interpret("spmv_bell")
        return real_ref(a_val, a_col, x, plan, out_rep=out_rep)

    monkeypatch.setattr(ozaki_spmv, "spmv_bell_ref", ref_spy)
    monkeypatch.setattr(ozaki_spmv, "spmv_bell", pallas_spy)
    return calls


def test_mode_scope_flips_spmv_route(monkeypatch):
    """mode_scope / REPRO_DISPATCH select the route of dispatch.spmv the
    same way they do for GEMM — no caller passes interpret= anymore."""
    calls = _spy_spmv_routes(monkeypatch)
    val = jnp.asarray(RNG.standard_normal((16, 4)))
    col = jnp.asarray(RNG.integers(0, 24, (16, 4)).astype(np.int32))
    x = jnp.asarray(RNG.standard_normal(24))

    with dispatch.mode_scope("xla"):
        dispatch.spmv(val, col, x)
    with dispatch.mode_scope("pallas"):
        dispatch.spmv(val, col, x)
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    dispatch.spmv(val, col, x)
    assert calls == ["xla", "pallas", "pallas"]


def test_mode_scope_flips_stencil_route(monkeypatch):
    from repro.kernels import ozaki_stencil

    calls = []
    real_ref = ozaki_stencil.stencil7_ref

    def ref_spy(*a, **kw):
        calls.append("xla")
        return real_ref(*a, **kw)

    def pallas_spy(u, c, plan, out_rep="f64", bx=1, interpret=True):
        calls.append("pallas")
        assert interpret == dispatch.pallas_interpret("stencil7")
        return real_ref(u, c, plan, out_rep=out_rep)

    monkeypatch.setattr(ozaki_stencil, "stencil7_ref", ref_spy)
    monkeypatch.setattr(ozaki_stencil, "stencil7", pallas_spy)

    u = jnp.asarray(RNG.standard_normal((4, 4, 4)))
    c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
    with dispatch.mode_scope("xla"):
        dispatch.stencil7(u, c)
    with dispatch.mode_scope("pallas"):
        dispatch.stencil7(u, c)
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    dispatch.stencil7(u, c)
    assert calls == ["xla", "pallas", "xla"]


def test_cg_solve_bell_rides_the_seam(monkeypatch):
    """The sparse-CG matvec goes through dispatch.spmv: mode_scope flips it."""
    from repro.hpc import spmv_formats
    from repro.hpc.cg import cg_solve_bell

    calls = _spy_spmv_routes(monkeypatch)
    dense = spmv_formats.laplacian_1d(12)
    val, col = spmv_formats.to_blocked_ell(dense, bw=4)
    b = jnp.asarray(RNG.standard_normal(12))
    with dispatch.mode_scope("pallas"):
        res = cg_solve_bell(jnp.asarray(val), jnp.asarray(col), b, tol=1e-10)
    assert res.converged
    assert calls and set(calls) == {"pallas"}


def test_stencil_routes_bit_identical():
    """xla vs pallas through dispatch.stencil7 — the cross-route parity the
    GEMM paths already pin, now for the structured-grid kind (all reps)."""
    u = jnp.asarray(RNG.standard_normal((10, 9, 11)))
    c = jnp.asarray(RNG.standard_normal(7))
    for rep in ("f64", "digits", "ds"):
        v_xla = np.asarray(dispatch.stencil7(u, c, out_rep=rep, mode="xla"))
        v_pal = np.asarray(dispatch.stencil7(u, c, out_rep=rep, bx=4,
                                             mode="pallas"))
        np.testing.assert_array_equal(v_xla, v_pal)


def _spmv_operator(kind):
    """(val, col, x): a random Blocked-ELL operator, or a banded one (the 3-D
    Poisson operator; wide and tall rectangles whose slots past the edge
    hold 0 and point at random columns)."""
    from repro.hpc import spmv_formats
    if kind == "random":
        val = RNG.standard_normal((24, 4))
        col = RNG.integers(0, 32, (24, 4)).astype(np.int32)
        return val, col, RNG.standard_normal(32)
    if kind == "poisson3d":
        val, col = spmv_formats.laplacian_3d_bell(3)
        return val, col, RNG.standard_normal(27)
    m, n, offsets = {"wide": (20, 30, (-3, 0, 7)),
                     "tall": (30, 20, (0, 2, -1))}[kind]
    col = np.arange(m)[:, None] + np.asarray(offsets)
    inside = (col >= 0) & (col < n)
    val = np.where(inside, RNG.standard_normal(col.shape), 0.0)
    col = np.where(inside, col, RNG.integers(0, n, col.shape)).astype(np.int32)
    return val, col, RNG.standard_normal(n)


@pytest.mark.parametrize("kind", ["random", "poisson3d", "wide", "tall"])
@pytest.mark.parametrize("out_rep", ["f64", "ds"])
def test_spmv_routes_bit_identical_small_plan(kind, out_rep):
    """xla vs pallas through dispatch.spmv with a 24-bit-payload plan (r = 7):
    small enough for the interpreted gather graph to compile in seconds, so
    the fast lane pins SpMV cross-route parity too (a second r = 7 geometry —
    ragged M, both reps, via the ops entry point — runs in the slow lane:
    test_kernels.py; the default r = 15 plan is uncoverable on CPU, its
    interpreter compile exceeds 10 minutes regardless of problem size).
    Banded operators take the pallas route's static shifts of x, the others
    its gather; both kernel outputs ("f64" writes digits, "ds" a pair)."""
    from repro.hpc import spmv_formats
    val, col, x = (jnp.asarray(t) for t in _spmv_operator(kind))
    offsets = spmv_formats.band_offsets(val, col)
    assert (offsets is None) == (kind == "random")
    plan = ozaki2.make_plan(val.shape[1], payload_bits=24, margin_bits=4)
    y_xla = np.asarray(dispatch.spmv(val, col, x, plan=plan, out_rep=out_rep,
                                     mode="xla"))
    y_pal = np.asarray(dispatch.spmv(val, col, x, plan=plan, out_rep=out_rep,
                                     br=8, mode="pallas", offsets=offsets))
    np.testing.assert_array_equal(y_xla, y_pal)


# ---------------------------------------------------------------------------
# Autotuning table (get_tuning / REPRO_TUNE)
# ---------------------------------------------------------------------------

@pytest.fixture
def tune_env(monkeypatch):
    """Set REPRO_TUNE and clear the memoised lookups, restoring both after."""
    def setter(value):
        monkeypatch.setenv(dispatch.TUNE_VAR, value)
        dispatch.clear_tune_cache()
    yield setter
    dispatch.clear_tune_cache()


def test_shape_class_buckets_to_next_pow2():
    assert dispatch.shape_class((100, 64, 24)) == "128x64x32"
    assert dispatch.shape_class((4096,)) == "4096"
    assert dispatch.shape_class((1,)) == "1"


def test_get_tuning_specific_class_overrides_wildcard():
    assert dispatch.get_tuning("reduce", (4096,))["block"] == 512
    assert dispatch.get_tuning("reduce", (65536,))["block"] == 256
    # 40000 buckets to the 65536 class
    assert dispatch.reduce_block(40000) == 256
    assert dispatch.reduce_block(4096) == 512


def test_get_tuning_rejects_unknown_kind():
    with pytest.raises(ValueError, match="tuning kind"):
        dispatch.get_tuning("fft", (64,))


def test_repro_tune_inline_json_overrides(tune_env):
    tune_env('{"reduce": {"*": {"block": 64}, "1024": {"block": 32}}}')
    assert dispatch.reduce_block(4096) == 64
    assert dispatch.reduce_block(1000) == 32    # class-specific beats wildcard


def test_repro_tune_file(tmp_path, tune_env):
    p = tmp_path / "tune.json"
    p.write_text('{"reduce": {"*": {"block": 128}}}')
    tune_env(str(p))
    assert dispatch.reduce_block(4096) == 128


def test_repro_tune_unknown_kind_raises(tune_env):
    tune_env('{"warp_drive": {"*": {"block": 64}}}')
    with pytest.raises(ValueError, match="unknown kind"):
        dispatch.reduce_block(4096)


def test_tuned_route_pin_wins_in_auto_mode(tune_env):
    plan = dispatch.get_plan(64)  # int8 substrate: pallas-capable
    # CPU's AUTO_ROUTE default for gemm is xla; a tuned entry pins pallas.
    tune_env('{"gemm": {"*": {"route": "pallas"}}}')
    assert dispatch.choose_route(plan, "gemm", shape=(128, 64, 128)) == "pallas"
    # ... but an explicit mode still wins over the table.
    assert dispatch.choose_route(plan, "gemm", mode="xla",
                                 shape=(128, 64, 128)) == "xla"


def test_tuned_route_invalid_value_raises(tune_env):
    plan = dispatch.get_plan(64)
    tune_env('{"gemm": {"*": {"route": "auto"}}}')
    # mode="auto" pins the table-consulting path: an ambient
    # REPRO_DISPATCH=xla|pallas (the CI matrix) would short-circuit before
    # the tuned-route validation and the expected ValueError would not fire.
    with pytest.raises(ValueError, match="tuned route"):
        dispatch.choose_route(plan, "gemm", mode="auto", shape=(128, 64, 128))


def test_reduce_kind_has_no_pallas_route():
    assert not dispatch.pallas_supported(None, "reduce")
    assert dispatch.choose_route(None, "reduce", mode="pallas") == "xla"


def test_choose_blocks_tuned_values_are_legality_clamped(tune_env):
    tune_env('{"gemm": {"*": {"bm": 100, "bn": 100, "bk": 100}}}')
    bm, bn, bk = dispatch.choose_blocks(512, 512, 512)
    assert bm == 104          # rounded up to the sublane granule (8)
    assert bn == 128          # rounded up to the lane granule (128)
    assert bk == 128          # lane-rounded and dividing the padded K
    # A bad tuning entry degrades performance, never correctness/legality.
    assert bm % dispatch.SUBLANE == 0 and bn % dispatch.LANE == 0


def test_tuned_blocks_keep_pallas_route_bit_identical(tune_env):
    a = jnp.asarray(RNG.standard_normal((16, 48)))
    b = jnp.asarray(RNG.standard_normal((48, 8)))
    want = np.asarray(dispatch.matmul(a, b, mode="xla"))
    tune_env('{"gemv": {"*": {"bm": 8, "bk": 128}}}')
    got = np.asarray(dispatch.matmul(a, b, mode="pallas"))
    np.testing.assert_array_equal(want, got)
