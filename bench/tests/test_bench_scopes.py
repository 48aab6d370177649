"""Device time by the program's named scopes: the HLO decoder on a trace
written here, the reduction on hand-made events, and both on an extract of a
trace recorded on the chip (``bench/tests/data/tpu_ops.json``)."""

import glob
import json
import os

import pytest

from bench import scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tpu_ops.json")
SCOPES = ("ozaki.split_a", "ozaki.split_b", "spmv.gather", "ozaki.finish", "reduce.dot2")


def test_scope_names_are_the_programs():
    from repro.obs import spans
    assert SCOPES == spans.SCOPES


@pytest.mark.parametrize("text,name", [
    ("%fusion.1 = s32[14680064]{0:T(1024)S(1)} fusion(s32[2097152]{0:T(1024)} "
     "%get-tuple-element.222), kind=kCustom, calls=%fused_computation.1", "fusion.1"),
    ("ROOT %custom-call.4 = f64[8,8]{1,0} custom-call(%a), "
     'custom_call_target="X64Combine"', "custom-call.4"),
    ("dynamic_slice.15", "dynamic_slice.15"),
])
def test_instruction_name(text, name):
    assert scopes.instruction_name(text) == name


def test_scope_is_a_whole_segment_of_the_op_name():
    assert scopes.has_scope("jit(f)/jit(_blocked_sum2)/reduce.dot2/while", "reduce.dot2")
    assert not scopes.has_scope("jit(f)/reduce.dot2x/add", "reduce.dot2")
    assert not scopes.has_scope("", "reduce.dot2")


def test_scope_seconds_on_hand_made_events():
    # Program p1 runs 0-50 ns, p2 60-100 ns; the window is 10..90 ns.
    modules = [("p1(1)", 0, 50), ("p2(2)", 60, 100)]
    ops = [("%fusion.1 = s32[8] fusion(%x)", 0, 20),      # p1: gather, 10-20 in window
           ("fusion.2", 20, 40),                          # p1: finish
           ("fusion.1", 60, 70),                          # p2: dot2 (same name, other program)
           ("dynamic_slice.4", 62, 66),                   # p2: dot2, inside fusion.1: once
           ("copy.3", 70, 95),                            # p2: no scope, clipped to 70-90
           ("fusion.9", 52, 58)]                          # between programs: unnamed
    op_names = {"p1(1)": {"fusion.1": "jit(spmv_bell)/spmv.gather/gather",
                          "fusion.2": "jit(spmv_bell)/ozaki.finish/mul"},
                "p2(2)": {"fusion.1": "jit(_blocked_sum2)/reduce.dot2/while",
                          "dynamic_slice.4":
                              "jit(_blocked_sum2)/reduce.dot2/while/body/dynamic_slice",
                          "copy.3": "jit(_blocked_sum2)/copy"}}
    sec = scopes.scope_seconds([(ops, modules)], op_names, SCOPES, 10, 90)
    assert sec == pytest.approx({"ozaki.split_a": 0.0, "ozaki.split_b": 0.0,
                                 "spmv.gather": 10e-9, "ozaki.finish": 20e-9,
                                 "reduce.dot2": 10e-9, "": 26e-9})
    # Averaged over the devices.
    two = scopes.scope_seconds([(ops, modules), (ops, modules)], op_names, SCOPES, 10, 90)
    assert two == pytest.approx(sec)


def test_program_op_names_from_a_trace_written_here(tmp_path):
    """The profiler keeps each program's HLO in ``/host:metadata``; the
    decoder finds the scope in the compensated dot's ops."""
    import jax
    import jax.numpy as jnp
    from repro.core import compensated

    jax.config.update("jax_enable_x64", True)
    x = jnp.linspace(0.0, 1.0, 300)
    f = jax.jit(compensated.compensated_dot)
    jax.block_until_ready(f(x, x))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(f(x, x))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        op_names = scopes.program_op_names(fh.read())
    dot = {k: v for k, v in op_names.items() if k.startswith("jit_compensated_dot(")}
    assert len(dot) == 1
    names, = dot.values()
    assert any(scopes.has_scope(op, "reduce.dot2") for op in names.values())
    # A trace with no TPU device reads no device time.
    sec = scopes.from_file(path, SCOPES, 0, float("inf"))
    assert set(sec) == set(SCOPES) | {""} and not any(sec.values())


@pytest.mark.parametrize("cell", ["cg_poisson7.n128", "dgemm.square"])
def test_chip_extract_scopes(cell):
    """On ops and programs recorded on a TPU v5e, each op counts under the
    scope its program's HLO gives it: the SpMV's gathers, the split of
    a_val and the Dot2 carry scan; the GEMM's splits and Garner finish.
    The kernels' custom calls and XLA's float32-pair split of a float64
    argument (op_name: the argument's name) count under none."""
    with open(DATA) as fh:
        ext = json.load(fh)[cell]
    ops = [tuple(op[:3]) for op in ext["ops"]]
    devices = [(ops, [tuple(m) for m in ext["modules"]])]
    sec = scopes.scope_seconds(devices, ext["op_names"], SCOPES, ext["lo"], ext["hi"])
    expected = {s: 0.0 for s in SCOPES + ("",)}
    for _, start, end, scope in ext["ops"]:
        expected[scope] += (end - start) * 1e-9
    assert sec == pytest.approx(expected, rel=1e-12, abs=1e-15)
    assert expected["spmv.gather" if cell.startswith("cg") else "ozaki.finish"] > 0
