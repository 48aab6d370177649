"""The trace reduction: on hand-made events, and on a profiler trace written
and read back here."""

import os
import subprocess
import sys

import pytest

from bench.trace import Event, Trace, device_event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace():
    # Window 0..100 ns; device ops 10-30, 20-40 (overlap), 60-70, and 90-120
    # (clipped to 90-100); a kernel's custom call, and an op that only
    # starts with its name.
    dev = [Event("fusion.1", 10, 30), Event("fusion.2", 20, 40),
           Event("gemm_hilo.1", 60, 70), Event("gemm_hilo_copy", 70, 72),
           Event("fusion.1", 90, 120)]
    host = {"main": [Event("bench.window", 0, 100), Event("bench.call", 0, 50),
                     Event("bench.call", 50, 100), Event("PjitFunction", 40, 48)],
            "other": [Event("ignored", 0, 100)]}
    return Trace([dev], host)


def test_window_and_busy_union():
    tr = _trace()
    assert tr.window_s == pytest.approx(100e-9)
    # Union: 10-40, 60-72, 90-100 = 52 ns.
    assert tr.busy_s == pytest.approx(52e-9)


def test_kernel_time_matches_the_op_name_and_its_number():
    tr = _trace()
    assert tr.kernel_s("gemm_hilo") == pytest.approx(10e-9)
    assert tr.kernel_s("fusion") == pytest.approx(50e-9)
    assert tr.kernel_s("fusion.1") == pytest.approx(30e-9)
    assert tr.kernel_s("absent") == 0.0


@pytest.mark.parametrize("text,name,label", [
    ('%gemm_hilo.1 = s8[16,1024,1024]{2,1,0:T(8,128)(4,1)S(1)} custom-call('
     '%get-tuple-element.186, %get-tuple-element.185), '
     'custom_call_target="tpu_custom_call", backend_config="..."',
     "gemm_hilo.1", "gemm_hilo.1 = s8[16,1024,1024] custom-call tpu_custom_call"),
    ('%convert_add_fusion.2 = (s32[8192,8192]{1,0:T(8,128)}, s32[8192,8192]'
     '{1,0:T(8,128)}) fusion(f32[8192,8192]{1,0:T(8,128)} %add_select_fusion.37), '
     'kind=kLoop, calls=%fused_computation.785', "convert_add_fusion.2",
     "convert_add_fusion.2 = (s32[8192,8192], s32[8192,8192]) fusion"),
    ('ROOT %custom-call.4 = f64[1024,1024]{1,0:T(8,128)} custom-call('
     '%add_select_fusion.10), custom_call_target="X64Combine"',
     "custom-call.4", "custom-call.4 = f64[1024,1024] custom-call X64Combine"),
    ("fusion.7", "fusion.7", ""),
])
def test_device_op_named_by_its_instruction(text, name, label):
    """A TPU trace gives a device op the whole text of its HLO instruction."""
    ev = device_event(text, 0, 10)
    assert (ev.name, ev.label) == (name, label)


def test_kernel_time_from_tpu_instruction_texts():
    dev = [device_event('%gemm_hilo.3 = s8[16,64,64]{2,1,0} custom-call(%p.1), '
                        'custom_call_target="tpu_custom_call"', 10, 60),
           device_event('%custom-call.2 = f32[64,64]{1,0} custom-call(%b.1), '
                        'custom_call_target="X64SplitHigh"', 60, 70)]
    tr = Trace([dev], {"main": [Event("bench.window", 0, 100)]})
    assert tr.kernel_s("gemm_hilo") == pytest.approx(50e-9)
    assert tr.top_device_ops() == [
        ["gemm_hilo.3 = s8[16,64,64] custom-call tpu_custom_call",
         pytest.approx(50e-9)],
        ["custom-call.2 = f32[64,64] custom-call X64SplitHigh", pytest.approx(10e-9)]]


def test_top_device_ops():
    assert _trace().top_device_ops(2) == [["fusion.1", pytest.approx(30e-9)],
                                          ["fusion.2", pytest.approx(20e-9)]]
    assert len(_trace().top_device_ops(10)) == 4


def test_idle_gaps_named_by_the_host():
    gaps = _trace().idle_gaps(10)
    # Gaps: 0-10, 40-60, 72-90; longest first; 40-60's middle (50) falls in
    # the second call, whose inner event (40-48) has ended.
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 18e-9, 10e-9])
    assert {g[0] for g in gaps} == {"bench.call"}


def test_gap_inside_a_host_event():
    tr = Trace([[Event("a", 0, 40), Event("b", 48, 100)]],
               {"main": [Event("bench.window", 0, 100), Event("bench.call", 0, 100),
                         Event("PjitFunction", 40, 48)]})
    assert tr.idle_gaps() == [["bench.call/PjitFunction", pytest.approx(8e-9)]]


def test_one_window_span_required():
    with pytest.raises(ValueError):
        Trace([[]], {"main": [Event("bench.call", 0, 1)]})


def test_no_device_reads_nothing():
    tr = Trace([], {"main": [Event("bench.window", 0, 100)]})
    assert tr.busy_s == 0.0 and tr.chips == 0 and tr.idle_gaps() == []


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A real profiler trace of a small jitted op on this machine, written
    and read back through ``Trace.from_file`` (the CPU has no device plane
    that the reduction reads)."""
    import jax
    import jax.numpy as jnp

    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return d


def test_trace_file_round_trip(recorded):
    tr = Trace.from_file(recorded)
    assert tr.window_s > 0
    assert tr.chips == 0 and tr.busy_s == 0.0
    names = {ev.name for ev in tr.host[tr.thread]}
    assert "bench.call" in names


def test_reduction_loads_no_tpu_library(recorded):
    code = ("import sys; sys.path.insert(0, %r); from bench.trace import Trace; "
            "Trace.from_file(%r); "
            "assert not [m for m in sys.modules if 'libtpu' in m], 'libtpu'"
            % (ROOT, recorded))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)
