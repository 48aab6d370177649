
import pytest

from repro.core import tme


P = tme.EmulationParams.ozaki2(r=10, substrate="fp8")


def test_table2_ridge_points():
    # Paper Table 2 bottom row: 10.1, 5.0, 0.16, 1.5 FLOPs/B.
    assert tme.H100.fp64_vector / tme.H100.hbm_tbps == pytest.approx(10.1, abs=0.1)
    assert tme.B200.fp64_vector / tme.B200.hbm_tbps == pytest.approx(5.0, abs=0.1)
    assert tme.B300.fp64_vector / tme.B300.hbm_tbps == pytest.approx(0.16, abs=0.01)
    assert tme.R200.fp64_vector / tme.R200.hbm_tbps == pytest.approx(1.5, abs=0.01)


def test_b300_emulation_ceiling():
    # §3: 5,000 / 10 = 500 TFLOPS dense on B300; 400 on Rubin.
    assert tme.emulated_perf(1000, tme.B300, P) == pytest.approx(500)
    assert tme.emulated_perf(1000, tme.R200, P) == pytest.approx(400)


def test_case_a_stencil_speedup():
    # §4.3 Case A worked example: I=0.5 on B300 -> 0.5*8/1.3 ≈ 3.1x.
    s = tme.speedup(0.5, tme.B300, P)
    assert s == pytest.approx(0.5 * 8 / 1.3, rel=1e-6)
    assert 3.0 < s < 3.2


def test_case_b_memory_bound_parity():
    # Case B: both memory-bound -> T_emu/T_nat -> β; fused β=1 gives parity.
    for spec in (tme.H100, tme.B200):
        assert tme.speedup(0.2, spec, P) == pytest.approx(1.0)
    unfused = tme.EmulationParams.ozaki2(r=10, substrate="fp8", fused=False)
    assert tme.speedup(0.2, tme.H100, unfused) == pytest.approx(1.0 / 10)


def test_case_c_compute_bound_gemm():
    # Case C on B300: ρ/α ≈ 5000/10/1.3 ≈ 380x (vs vector; table uses ~380).
    s = tme.speedup(1000, tme.B300, P, matrix=False)
    assert s == pytest.approx(500 / 1.3, rel=1e-6)


def test_table3_b300_column():
    rows = {r["workload"]: r for r in tme.table3_speedups()}
    assert rows["dense_gemm"]["B300"] == pytest.approx(500 / 1.2, rel=0.01)
    assert rows["bgemv_b8"]["B300"] == pytest.approx(24.6, rel=0.02)
    assert rows["bgemv_b2"]["B300"] == pytest.approx(9.2, rel=0.02)
    assert rows["stencil_7pt"]["B300"] == pytest.approx(3.1, rel=0.02)
    assert rows["spmv"]["B300"] == pytest.approx(1.23, rel=0.02)


def test_table4_key_cells():
    rows = tme.table4_h100_baseline()
    def cell(work, path, chip):
        for r in rows:
            if r["workload"] == work and r["path"] == path:
                return r[chip]
        raise KeyError

    # Paper Table 4 spot checks.
    assert cell("dense_gemm", "native", "H100") == pytest.approx(67)
    assert cell("dense_gemm", "ozaki2", "H100") == pytest.approx(198, rel=0.01)
    assert cell("dense_gemm", "ozaki2", "B300") == pytest.approx(500)
    assert cell("bgemv_b8", "ozaki2", "B300") == pytest.approx(32)
    assert cell("bgemv_b8", "ozaki2", "R200") == pytest.approx(88)
    assert cell("stencil_7pt", "ozaki2", "R200") == pytest.approx(11)
    assert cell("spmv", "ozaki2", "B300") == pytest.approx(1.6)
    # H100-relative: memory-bound rows on B300 = HBM ratio 8/3.35 = 2.39x.
    assert cell("stencil_7pt", "ozaki2", "B300") / cell("stencil_7pt", "native", "H100") \
        == pytest.approx(8 / 3.35, rel=0.01)
    # Rubin memory-bound rows = 22/3.35 = 6.57x.
    assert cell("spmv", "ozaki2", "R200") / cell("spmv", "native", "H100") \
        == pytest.approx(22 / 3.35, rel=0.01)


def test_table5():
    rows = {r["chip"]: r for r in tme.table5_substrates()}
    assert rows["H100"]["fp8_advantage"] == pytest.approx(1.0)
    assert rows["B300"]["fp8_advantage"] == pytest.approx(30.3, rel=0.02)
    assert rows["B200"]["fp8_advantage"] == pytest.approx(29.0, rel=0.02)
    assert rows["R200"]["fp8_advantage"] == pytest.approx(16.0, rel=0.02)
    assert rows["B300"]["ozaki_fp8_ceiling"] == pytest.approx(500)


def test_moduli_sensitivity_section_2_4():
    rows = {r["r"]: r for r in tme.moduli_sensitivity("B300")}
    # r=11: ceiling drops ~9% (500 -> ~455); r=12: ~17%.
    assert rows[11]["ceiling_r"] == pytest.approx(455, rel=0.01)
    assert rows[12]["ceiling_r"] == pytest.approx(417, rel=0.01)


def test_emulated_perf_never_exceeds_roofs():
    for oi in (0.01, 0.2, 1.5, 18, 100, 1e4):
        for spec in tme.CHIPS.values():
            e = tme.emulated_perf(oi, spec, P)
            assert e <= oi * spec.hbm_tbps + 1e-9
            assert e <= tme.p_low(spec, "fp8") / P.alpha + 1e-9


def test_emulation_ridge():
    # B300: P_fp8/(r·B_mem) = 5000/(10·8) = 62.5 F/B.
    assert tme.emulation_ridge(tme.B300, P) == pytest.approx(62.5)
    # §4.4's "I ≲ 18 FLOPS/B" figure corresponds to Rubin: 4000/(10·22) ≈ 18.2.
    assert tme.emulation_ridge(tme.R200, P) == pytest.approx(18.2, rel=0.01)


def test_roofline_terms():
    t = tme.roofline_terms(hlo_flops=1e15, hlo_bytes=1e12, collective_bytes=1e11,
                           chips=256)
    assert t.compute_s == pytest.approx(1e15 / (256 * 197e12))
    assert t.memory_s == pytest.approx(1e12 / (256 * 819e9))
    assert t.collective_s == pytest.approx(1e11 / (256 * 50e9))
    assert t.dominant == "compute"


def test_bailey_fft_stages_inventory():
    # 1024 = 32*32, both factors dense: one recursion level, two GEMM leaves.
    stages = tme.bailey_fft_stages(1024, batch=8)
    assert [s.name for s in stages] == ["gemm_n32", "twiddle_n1024",
                                        "transpose_n1024", "gemm_n32"]
    # each dense leaf: 8f MACs-worth of FLOPs per element of the full stack
    assert stages[0].W == stages[3].W == 8.0 * 32 * 1024 * 8
    # each GEMM pass reconstructs 2n real outputs per batch element
    assert stages[0].n_out == 2.0 * 1024 * 8
    assert stages[2].W == 0.0          # transpose is pure data movement


def test_bailey_fft_stages_recurse_like_the_executed_transform():
    """Model stages mirror dft_stacked's recursion: 2^18 -> 512*512 with each
    512 factored again (16*32), so GEMM leaves are all dense-sized."""
    from repro.spectral.dft import DENSE_MAX
    stages = tme.bailey_fft_stages(1 << 18)
    names = [s.name for s in stages]
    assert "twiddle_n262144" in names and "twiddle_n512" in names
    leaf_sizes = {int(s.name[len("gemm_n"):]) for s in stages
                  if s.name.startswith("gemm_n")}
    assert leaf_sizes == {16, 32}
    assert all(f <= DENSE_MAX for f in leaf_sizes)


def test_fft_gamma_term_not_silently_zero():
    """The per-stage gamma split must be visible under the model defaults."""
    rows = tme.table_fft(r=10, batch=4096, sizes=(1 << 18,))
    assert all(r["gamma_fraction"] > 0.0 for r in rows)
    assert all(r["gamma_fraction"] < 0.5 for r in rows)   # amortised, not dominant
    assert tme.garner_gamma(tme.B300, 10) == pytest.approx(100 / 165e12)


def test_fft_emulated_beats_native_on_post_fp64_chips():
    """The companion-paper claim in TME terms: emulation loses on H100's
    healthy FP64 pipe and wins on B300 where FP64 has collapsed."""
    import dataclasses
    for chip, expect_win in (("H100", False), ("B300", True)):
        spec = tme.CHIPS[chip]
        params = dataclasses.replace(
            tme.EmulationParams.ozaki2(r=10, substrate="fp8"),
            gamma=tme.garner_gamma(spec, 10))
        nat = tme.fft_native_time(1 << 18, spec, batch=4096)
        emu = tme.fft_emulated_time(1 << 18, spec, params, batch=4096)
        assert (nat / emu > 1.0) == expect_win


# --- native_ridge / telemetry prediction surface -----------------------------

def test_native_ridge_pins_h100_table2_value():
    """TFLOPS / (TB/s): the 1e12s cancel, leaving FLOPs/Byte — H100's Table 2
    ridge is 34/3.35 ≈ 10.1 F/B (regression pin for the old unit-fudge bug)."""
    assert tme.H100.native_ridge == pytest.approx(34 / 3.35)
    assert tme.H100.native_ridge == pytest.approx(10.1, abs=0.1)
    for spec in tme.CHIPS.values():
        assert spec.native_ridge == pytest.approx(
            spec.fp64_vector / spec.hbm_tbps)


def test_default_chip_env_selection(monkeypatch):
    monkeypatch.delenv(tme.CHIP_VAR, raising=False)
    assert tme.default_chip().name == "TPUv5e"
    monkeypatch.setenv(tme.CHIP_VAR, "H100")
    assert tme.default_chip() is tme.H100
    monkeypatch.setenv(tme.CHIP_VAR, "Z9000")
    with pytest.raises(ValueError, match="REPRO_TME_CHIP"):
        tme.default_chip()


def test_op_costs_per_kind():
    assert tme.op_costs("gemm", (4, 5, 6)) == (240.0, 8.0 * (20 + 30 + 24),
                                               24.0)
    assert tme.op_costs("gemv", (4, 5, 1)) == (40.0, 8.0 * (20 + 5 + 4), 4.0)
    W, Q, n_out = tme.op_costs("spmv_bell", (8, 4, 16))
    assert (W, n_out) == (64.0, 8.0)
    assert Q == 8 * 4 * 8 + 8 * 4 * 4 + 16 * 8 + 8 * 8
    W, Q, n_out = tme.op_costs("stencil7", (2, 3, 4))
    assert (W, Q, n_out) == (14.0 * 24, 16.0 * 24, 24.0)
    assert tme.op_costs("reduce", (100,)) == (200.0, 1600.0, 1.0)
    # attention (B, S, D, T): QK^T + PV flops, q/k/v/out f64 traffic.
    W, Q, n_out = tme.op_costs("attention", (2, 12, 16, 12))
    assert W == 4.0 * 2 * 12 * 12 * 16
    assert Q == 8.0 * 2 * (2 * 12 * 16 + 2 * 12 * 16)
    assert n_out == 2 * 12 * (12 + 16)
    # 3-tuple (S, D, T) means batch 1 (the dispatch entry always passes B).
    assert tme.op_costs("attention", (12, 16, 12)) == \
        tme.op_costs("attention", (1, 12, 16, 12))
    with pytest.raises(ValueError):
        tme.op_costs("fft", (8,))


def test_predict_op_time_route_beta_ordering():
    """xla (unfused, β = r) must predict ≥ pallas (fused, β = 1) for the same
    op on a memory-ridge-bound chip, and both must be positive and finite."""
    dims = (128, 256, 128)
    t_xla = tme.predict_op_time("gemm", dims, r=15, route="xla",
                                spec=tme.TPU_V5E)
    t_pal = tme.predict_op_time("gemm", dims, r=15, route="pallas",
                                spec=tme.TPU_V5E)
    assert 0.0 < t_pal < t_xla


def test_attention_emulated_time_routes_and_orders():
    """Both routes pay the materialised S/P matrices once; the xla route's
    GEMMs also write r residue planes (β = r against the pallas GEMM
    kernels' β = 1) — so xla ≥ pallas ≥ the S/P-free op, and predict_op_time
    delegates to attention_emulated_time for kind="attention"."""
    dims = (1, 64, 32, 64)
    t_xla = tme.attention_emulated_time(dims, r=15, route="xla",
                                        spec=tme.TPU_V5E)
    t_pal = tme.attention_emulated_time(dims, r=15, route="pallas",
                                        spec=tme.TPU_V5E)
    assert 0.0 < t_pal < t_xla
    W, Q, n_out = tme.op_costs("attention", dims)
    params = tme.EmulationParams(alpha=15.0, beta=1.0,
                                 gamma=tme.garner_gamma(tme.TPU_V5E, 15),
                                 substrate="int8")
    assert t_pal > tme.emulated_time(W, Q, n_out, tme.TPU_V5E, params)
    assert tme.predict_op_time("attention", dims, r=15, route="xla",
                               spec=tme.TPU_V5E) == pytest.approx(t_xla)
    assert tme.predict_op_time("attention", dims, r=15, route="pallas",
                               spec=tme.TPU_V5E) == pytest.approx(t_pal)


def test_predict_op_time_reduce_has_no_garner_term():
    """reduce is the §7.1(a) EFT path: no emulation, so prediction scales
    linearly in n (γ = 0 — no per-output reconstruction offset)."""
    t1 = tme.predict_op_time("reduce", (1 << 12,), spec=tme.TPU_V5E)
    t2 = tme.predict_op_time("reduce", (1 << 13,), spec=tme.TPU_V5E)
    assert t2 == pytest.approx(2 * t1, rel=1e-6)
