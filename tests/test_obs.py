"""Observability tests.  Telemetry: mode resolution, recording tiers, tracer
safety (the instrumented entry points must still jit, bit-identically), cache
counters, solver residual traces, serving events, and the report/probe
surfaces.  Spans: the device scopes in each kind's HLO metadata, and the CG
loop's host spans in a profiler trace."""

import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compensated, dispatch, ozaki2
from repro.hpc import cg, jacobi
from repro.kernels import ozaki_gemm, ozaki_gemv, ozaki_spmv, ozaki_stencil
from repro.obs import report, spans, telemetry as obs


@pytest.fixture(autouse=True)
def clean_telemetry(monkeypatch):
    """Every test starts with empty stores, no TLS override, and no ambient
    REPRO_TELEMETRY leaking in from the environment."""
    monkeypatch.delenv(obs.ENV_VAR, raising=False)
    obs.set_mode(None)
    obs.reset()
    yield
    obs.set_mode(None)
    obs.reset()


def _rng():
    return np.random.default_rng(0)


def _gemm_operands(n=32):
    rng = _rng()
    return (jnp.asarray(rng.standard_normal((n, n))),
            jnp.asarray(rng.standard_normal((n, n))))


# --- mode resolution ---------------------------------------------------------

def test_mode_default_off():
    assert obs.get_mode() == "off"
    assert not obs.enabled()


def test_mode_from_env(monkeypatch):
    monkeypatch.setenv(obs.ENV_VAR, "counters")
    assert obs.get_mode() == "counters"
    assert obs.enabled()


def test_mode_env_invalid_raises(monkeypatch):
    monkeypatch.setenv(obs.ENV_VAR, "loud")
    with pytest.raises(ValueError, match="telemetry mode"):
        obs.get_mode()


def test_set_mode_overrides_env(monkeypatch):
    monkeypatch.setenv(obs.ENV_VAR, "trace")
    obs.set_mode("off")
    assert obs.get_mode() == "off"
    obs.set_mode(None)
    assert obs.get_mode() == "trace"


def test_scope_nests_and_restores():
    with obs.telemetry_scope("counters"):
        assert obs.get_mode() == "counters"
        with obs.telemetry_scope("trace"):
            assert obs.get_mode() == "trace"
        with obs.telemetry_scope(None):      # None inherits
            assert obs.get_mode() == "counters"
        assert obs.get_mode() == "counters"
    assert obs.get_mode() == "off"


def test_scope_invalid_mode_raises():
    with pytest.raises(ValueError):
        with obs.telemetry_scope("verbose"):
            pass


# --- recording tiers ---------------------------------------------------------

def test_off_records_nothing():
    a, b = _gemm_operands()
    dispatch.matmul(a, b, mode="xla")
    assert obs.counters_snapshot() == {}
    assert obs.trace_snapshot() == []
    assert obs.cache_snapshot() == {}


def test_counters_mode_aggregates_without_trace():
    a, b = _gemm_operands()
    with obs.telemetry_scope("counters"):
        dispatch.matmul(a, b, mode="xla")
        dispatch.matmul(a, b, mode="xla")
    counters = obs.counters_snapshot()
    key = ("gemm", dispatch.shape_class((32, 32, 32)), "xla")
    assert key in counters
    agg = counters[key]
    assert agg["calls"] == 2
    assert agg["us"] > 0.0
    assert agg["us_min"] <= agg["us_max"] <= agg["us"]
    assert agg["flops"] == pytest.approx(2 * 2.0 * 32 ** 3)
    assert agg["tme_us"] > 0.0
    assert obs.trace_snapshot() == []        # ring only fills in trace mode


def test_trace_mode_fills_ring_with_plan_fields():
    a, b = _gemm_operands()
    with obs.telemetry_scope("trace"):
        dispatch.matmul(a, b, mode="xla")
    (ev,) = [e for e in obs.trace_snapshot() if e.kind == "gemm"]
    plan = dispatch.get_plan(32)
    assert ev.route == "xla"
    assert ev.r == plan.r
    assert ev.payload_bits == plan.payload_bits
    assert ev.us > 0.0 and ev.tme_us > 0.0
    assert ev.shape_class == dispatch.shape_class((32, 32, 32))


def test_all_dispatch_kinds_record(tmp_path):
    rng = _rng()
    a, b = _gemm_operands()
    v = jnp.asarray(rng.standard_normal((32, 2)))
    u = jnp.asarray(rng.standard_normal((8, 8, 8)))
    c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
    plan_r7 = ozaki2.make_plan(4, payload_bits=24, margin_bits=4)
    val = jnp.asarray(rng.standard_normal((32, 4)))
    col = jnp.asarray(rng.integers(0, 32, (32, 4)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(32))
    q = jnp.asarray(rng.standard_normal((16, 8)))
    kq = jnp.asarray(rng.standard_normal((16, 8)))
    vq = jnp.asarray(rng.standard_normal((16, 8)))
    with obs.telemetry_scope("counters"):
        dispatch.matmul(a, b, mode="xla")
        dispatch.matmul(a, v, mode="xla")
        dispatch.stencil7(u, c, bx=4, mode="xla")
        dispatch.spmv(val, col, x, plan=plan_r7, br=8, mode="xla")
        dispatch.attention(q, kq, vq, mode="xla")
        compensated.compensated_dot(x, x)
    kinds = {k for (k, _, _) in obs.counters_snapshot()}
    assert {"gemm", "gemv", "stencil7", "spmv_bell", "attention",
            "reduce"} <= kinds


def test_attention_labels_prefill_vs_decode():
    rng = _rng()
    k = jnp.asarray(rng.standard_normal((16, 8)))
    v = jnp.asarray(rng.standard_normal((16, 8)))
    q_pre = jnp.asarray(rng.standard_normal((16, 8)))
    q_dec = jnp.asarray(rng.standard_normal((1, 8)))
    with obs.telemetry_scope("trace"):
        dispatch.attention(q_pre, k, v, mode="xla")
        dispatch.attention(q_dec, k, v, mode="xla")
    labels = [e.label for e in obs.trace_snapshot() if e.kind == "attention"]
    assert labels == ["prefill", "decode"]
    events = [e for e in obs.trace_snapshot() if e.kind == "attention"]
    assert all(e.tme_us > 0.0 for e in events)


def test_reduce_labels_cover_sum_dot_norm():
    x = jnp.asarray(_rng().standard_normal(256), jnp.float32)
    with obs.telemetry_scope("trace"):
        compensated.neumaier_sum(x)
        compensated.compensated_dot(x, x)
        compensated.compensated_norm(x)
    labels = [e.label for e in obs.trace_snapshot() if e.kind == "reduce"]
    # norm must record exactly one event (not a nested dot2 as well)
    assert labels == ["sum2", "dot2", "nrm2"]


def test_reset_clears_everything():
    a, b = _gemm_operands()
    with obs.telemetry_scope("trace"):
        dispatch.matmul(a, b, mode="xla")
        obs.record_event("custom", us=1.0)
    obs.reset()
    assert obs.counters_snapshot() == {}
    assert obs.trace_snapshot() == []
    assert obs.cache_snapshot() == {}


# --- tracer safety (satellite: bit-identity under jit) -----------------------

@pytest.mark.parametrize("op", ["matmul", "spmv", "stencil7", "attention",
                                "dot"])
def test_jit_bit_identical_and_silent(op):
    """Under jax.jit with telemetry on: nothing is recorded (operands are
    tracers) and the result is bit-identical to telemetry off."""
    rng = _rng()
    if op == "matmul":
        a, b = _gemm_operands()
        fn = jax.jit(lambda a, b: dispatch.matmul(a, b, mode="xla"))
        args = (a, b)
    elif op == "spmv":
        plan_r7 = ozaki2.make_plan(4, payload_bits=24, margin_bits=4)
        val = jnp.asarray(rng.standard_normal((32, 4)))
        col = jnp.asarray(rng.integers(0, 32, (32, 4)).astype(np.int32))
        x = jnp.asarray(rng.standard_normal(32))
        fn = jax.jit(lambda val, col, x: dispatch.spmv(
            val, col, x, plan=plan_r7, br=8, mode="xla"))
        args = (val, col, x)
    elif op == "stencil7":
        u = jnp.asarray(rng.standard_normal((8, 8, 8)))
        c = jnp.asarray(np.array([6.0, -1, -1, -1, -1, -1, -1]))
        fn = jax.jit(lambda u, c: dispatch.stencil7(u, c, bx=4, mode="xla"))
        args = (u, c)
    elif op == "attention":
        q = jnp.asarray(rng.standard_normal((16, 8)))
        k = jnp.asarray(rng.standard_normal((16, 8)))
        v = jnp.asarray(rng.standard_normal((16, 8)))
        fn = jax.jit(lambda q, k, v: dispatch.attention(q, k, v, mode="xla"))
        args = (q, k, v)
    else:
        x = jnp.asarray(rng.standard_normal(512), jnp.float32)
        fn = jax.jit(compensated.compensated_dot)
        args = (x, x)

    ref = jax.block_until_ready(fn(*args))        # telemetry off
    obs.reset()
    with obs.telemetry_scope("trace"):
        out = jax.block_until_ready(fn(*args))
        assert obs.counters_snapshot() == {}, "jitted call must record nothing"
        assert obs.trace_snapshot() == []
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_record_event_drops_tracer_payloads():
    @jax.jit
    def f(x):
        obs.record_event("inside", value=x)      # x is a tracer here
        return x * 2
    with obs.telemetry_scope("trace"):
        f(jnp.ones(4))
    assert all(e.kind != "inside" for e in obs.trace_snapshot())


# --- cache counters ----------------------------------------------------------

def test_plan_and_tune_cache_counters():
    dispatch.clear_plan_cache()
    dispatch.clear_tune_cache()
    with obs.telemetry_scope("counters"):
        dispatch.get_plan(24)
        dispatch.get_plan(24)
        dispatch.get_tuning("gemm", (16, 24, 16))
        dispatch.get_tuning("gemm", (16, 24, 16))
    caches = obs.cache_snapshot()
    assert caches["plan"] == (1, 1)              # (hits, misses)
    assert caches["tune"] == (1, 1)


def test_spmv_band_cache_counter():
    """``spmv_band`` counts a hit where the pallas SpMV reads x by static
    shifts (a banded operator), a miss where it gathers."""
    from repro.hpc import spmv_formats
    rng = _rng()
    plan_r7 = ozaki2.make_plan(7, payload_bits=24, margin_bits=4)
    banded = [jnp.asarray(t) for t in spmv_formats.laplacian_3d_bell(2)]
    general = [jnp.asarray(rng.standard_normal((8, 7))),
               jnp.asarray(rng.integers(0, 8, (8, 7)).astype(np.int32))]
    x = jnp.asarray(rng.standard_normal(8))
    with obs.telemetry_scope("counters"):
        for val, col in (banded, general):
            dispatch.spmv(val, col, x, plan=plan_r7, br=8, mode="pallas",
                          offsets=spmv_formats.band_offsets(val, col))
    assert obs.cache_snapshot()["spmv_band"] == (1, 1)   # (hits, misses)


def test_spmv_vmem_cache_counter(monkeypatch):
    """``spmv_vmem`` counts a hit where the pallas SpMV gathers x inside the
    kernel (a general operator, short x), a miss for a banded operator and
    for an x longer than ``X_VMEM_MAX``."""
    from repro.hpc import spmv_formats
    rng = _rng()
    plan_r7 = ozaki2.make_plan(7, payload_bits=24, margin_bits=4)
    banded = [jnp.asarray(t) for t in spmv_formats.laplacian_3d_bell(2)]
    general = [jnp.asarray(rng.standard_normal((8, 7))),
               jnp.asarray(rng.integers(0, 8, (8, 7)).astype(np.int32))]
    x = jnp.asarray(rng.standard_normal(8))
    try:
        with obs.telemetry_scope("counters"):
            for val, col in (general, banded):
                dispatch.spmv(val, col, x, plan=plan_r7, br=8, mode="pallas",
                              offsets=spmv_formats.band_offsets(val, col))
            monkeypatch.setattr(ozaki_spmv, "X_VMEM_MAX", 7)
            ozaki_spmv.spmv_bell.clear_cache()       # the route is traced in
            dispatch.spmv(*general, x, plan=plan_r7, br=8, mode="pallas")
    finally:
        ozaki_spmv.spmv_bell.clear_cache()
    assert obs.cache_snapshot()["spmv_vmem"] == (1, 2)   # (hits, misses)


@pytest.mark.parametrize("bw", [1, 7, 8, 404])
def test_x_in_vmem_threshold_boundary(bw):
    """The route holds up to X_VMEM_MAX entries of x for a whole number of
    8-slot tiles; padding bw to tiles shrinks the limit in proportion."""
    br, most = 128, ozaki_spmv.X_VMEM_MAX
    tiles = -(-bw // 8) * 8
    n = most * bw // tiles // br * br               # the longest x that fits
    assert ozaki_spmv.x_in_vmem(n, bw, br)
    assert not ozaki_spmv.x_in_vmem(n + 1, bw, br)


# --- solver residual traces --------------------------------------------------

def test_cg_residual_trace_matches_history():
    rng = _rng()
    n = 12
    m = rng.standard_normal((n, n))
    a = jnp.asarray(m @ m.T + n * np.eye(n))
    b = jnp.asarray(rng.standard_normal(n))
    with obs.telemetry_scope("trace"):
        res = cg.cg_solve_dense(a, b, tol=1e-10, maxiter=2 * n, mode="xla",
                                record_plain=False)
    events = [e for e in obs.trace_snapshot() if e.kind == "solver.cg"]
    assert len(events) == len(res.history)
    iters = [dict(e.extra)["iter"] for e in events]
    assert iters == list(range(len(res.history)))
    rels = [dict(e.extra)["rel_residual"] for e in events]
    assert rels == pytest.approx(res.history)


def test_jacobi_residual_trace_matches_history():
    rng = _rng()
    f = jnp.asarray(rng.standard_normal((6, 6, 6)))
    with obs.telemetry_scope("trace"):
        res = jacobi.jacobi_solve(f, tol=1e-6, maxiter=50, mode="xla",
                                  check_every=5)
    events = [e for e in obs.trace_snapshot() if e.kind == "solver.jacobi"]
    assert len(events) == len(res.history)
    assert dict(events[0].extra)["rel_residual"] == pytest.approx(
        res.history[0])


# --- serving events ----------------------------------------------------------

def test_serve_engine_records_step_events():
    from repro.configs import registry
    from repro.models.transformer import Model
    from repro.serve.engine import ContinuousBatcher, Request, ServeEngine

    cfg = registry.get_config("yi-6b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_slots=2, max_seq=32)
    cb = ContinuousBatcher(eng)
    rng = _rng()
    with obs.telemetry_scope("trace"):
        cb.submit(Request(uid=0, max_new_tokens=2, prompt=rng.integers(
            0, cfg.vocab_size, 3).astype(np.int32)))
        done = cb.run_to_completion(max_steps=20)
    assert len(done) == 1
    events = obs.trace_snapshot()
    prefill = [e for e in events if e.kind == "serve.prefill"]
    decode = [e for e in events if e.kind == "serve.decode"]
    queue = [e for e in events if e.kind == "serve.queue"]
    assert len(prefill) == 1
    assert dict(prefill[0].extra)["tokens"] == 3
    assert prefill[0].us > 0.0
    assert dict(prefill[0].extra)["tokens_per_s"] > 0.0
    assert len(decode) >= 1 and all(e.us > 0.0 for e in decode)
    assert dict(queue[0].extra) == {"queued": 1, "active": 0}


# --- report / probe / snapshot -----------------------------------------------

def test_report_rows_and_render():
    a, b = _gemm_operands()
    with obs.telemetry_scope("counters"):
        dispatch.matmul(a, b, mode="xla")
        obs.record_event("solver.cg", dims=(16,), iter=0, rel_residual=1.0)
    rows = report.table_rows()
    by_kind = {r["kind"]: r for r in rows}
    assert by_kind["gemm"]["ratio"] > 0.0
    assert by_kind["solver.cg"]["ratio"] == 0.0   # no TME prediction
    text = report.render(rows, chip="TPUv5e")
    assert "gemm" in text and "TPUv5e" in text


def test_probe_returns_route_event():
    a, b = _gemm_operands()
    out, ev = obs.probe(lambda: dispatch.matmul(a, b, mode="pallas"))
    assert ev is not None
    assert ev.route == "pallas" and ev.kind == "gemm"
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(dispatch.matmul(a, b, mode="xla")))
    assert obs.get_mode() == "off"                # probe restores the mode


def test_probe_no_dispatch_returns_none():
    out, ev = obs.probe(lambda: jnp.ones(3) * 2)
    assert ev is None
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones(3))


def test_snapshot_json_roundtrip_and_report_main(tmp_path, capsys):
    a, b = _gemm_operands()
    with obs.telemetry_scope("trace"):
        dispatch.matmul(a, b, mode="xla")
        path = obs.write_json(str(tmp_path / "telemetry.json"))
    snap = json.loads((tmp_path / "telemetry.json").read_text())
    assert snap["mode"] == "trace"
    assert snap["counters"] and snap["trace"]
    assert snap["chip"] in ("TPUv5e", "H100", "B200", "B300", "R200")
    assert report.main([path]) == 0
    assert "gemm" in capsys.readouterr().out
    assert report.main([path, "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert rows[0]["kind"] == "gemm"


# --- spans: device scopes and host spans --------------------------------------

def _scope_lowering(kind):
    """A small lowering of one kind's jitted program (interpret-mode Pallas,
    24-bit plans so the residue graphs stay small)."""
    f64 = jnp.float64
    if kind == "gemm":
        plan = ozaki2.make_plan(32, payload_bits=24)
        a = jnp.ones((32, 32), f64)
        return jax.jit(lambda a, b: ozaki_gemm.gemm(a, b, plan, interpret=True)
                       ).lower(a, a)
    if kind == "matmul_pallas":
        plan = ozaki2.make_plan(32, payload_bits=24)
        a = jnp.ones((32, 32), f64)
        return jax.jit(lambda a, b: dispatch.matmul(a, b, plan, mode="pallas")
                       ).lower(a, a)
    if kind == "gemv":
        plan = ozaki2.make_plan(32, payload_bits=24)
        return jax.jit(lambda a, x: ozaki_gemv.gemv(a, x, plan, interpret=True)
                       ).lower(jnp.ones((32, 32), f64), jnp.ones((32, 4), f64))
    if kind == "spmv_bell":
        plan = ozaki2.make_plan(7, payload_bits=24)
        return ozaki_spmv.spmv_bell.lower(jnp.ones((64, 7), f64),
                                          jnp.zeros((64, 7), jnp.int32),
                                          jnp.ones(64, f64), plan, interpret=True)
    if kind == "stencil7":
        plan = ozaki2.make_plan(8, payload_bits=24)
        return ozaki_stencil.stencil7.lower(jnp.ones((8, 8, 128), f64),
                                            jnp.ones(7, f64), plan, interpret=True)
    x = jnp.ones(300, f64)
    return jax.jit(compensated.compensated_dot).lower(x, x)


@pytest.mark.parametrize("kind,expected", [
    ("gemm", {"ozaki.split_a", "ozaki.split_b", "ozaki.residues", "ozaki.finish"}),
    ("matmul_pallas", {"ozaki.split_a", "ozaki.split_b", "ozaki.residues",
                       "ozaki.finish"}),
    ("gemv", {"ozaki.split_a", "ozaki.split_b", "ozaki.finish"}),
    ("spmv_bell", {"ozaki.split_a", "ozaki.split_b", "spmv.gather", "ozaki.finish"}),
    ("stencil7", {"ozaki.split_a", "ozaki.split_b", "ozaki.finish"}),
    ("dot2", {"reduce.dot2"}),
])
def test_scopes_in_hlo_op_name_metadata(kind, expected):
    """Each phase's scope is a segment of its ops' HLO ``op_name`` paths."""
    text = _scope_lowering(kind).compiler_ir("hlo").as_hlo_module().to_string()
    segments = {seg for path in re.findall(r'op_name="([^"]*)"', text)
                for seg in path.split("/")}
    assert segments & set(spans.SCOPES) == expected


def test_unknown_span_or_scope_name_raises():
    with pytest.raises(ValueError, match="unknown scope"):
        spans.scope("ozaki.split")
    with pytest.raises(ValueError, match="unknown span"):
        spans.span("cg")


@pytest.mark.parametrize("record_plain,syncs_per_iter", [(False, 1), (True, 2)])
def test_cg_host_spans_in_profiler_trace(tmp_path, record_plain, syncs_per_iter):
    """One ``repro.cg.iter`` span an iteration, and one ``repro.sync`` span a
    host read: before the loop and in each iteration, twice with the plain
    shadow history."""
    from jax.profiler import ProfileData

    n, iters = 16, 4
    a = jnp.asarray(np.diag(np.arange(1.0, n + 1)))
    b = jnp.ones(n)
    cg.cg_solve(lambda v: a @ v, b, tol=0.0, maxiter=1)       # compile first
    with jax.profiler.trace(str(tmp_path)):
        res = cg.cg_solve(lambda v: a @ v, b, tol=0.0, maxiter=iters,
                          record_plain=record_plain)
    assert res.iters == iters
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    counts = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    assert counts == {"repro.cg.iter": iters,
                      "repro.sync": syncs_per_iter * (iters + 1)}


def test_npb_power_step_records_one_outer_span(tmp_path):
    """One ``repro.npb.outer`` span an NPB outer step, around its CG
    iterations' ``repro.cg.iter`` spans."""
    from jax.profiler import ProfileData

    from repro.hpc import npb_cg, spmv_formats

    val, col = (jnp.asarray(t) for t in spmv_formats.laplacian_3d_bell(3))
    x = jnp.ones(27)
    npb_cg.power_step(val, col, x, 10.0, cg_iters=1)          # compile first
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(npb_cg.power_step(val, col, x, 10.0, cg_iters=3))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans_seen = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name in ("repro.npb.outer", "repro.cg.iter")]
    outer = [(s, e) for s, e, name in spans_seen if name == "repro.npb.outer"]
    inner = [(s, e) for s, e, name in spans_seen if name == "repro.cg.iter"]
    assert len(outer) == 1 and len(inner) == 3
    (lo, hi), = outer
    assert all(lo <= s and e <= hi for s, e in inner)
