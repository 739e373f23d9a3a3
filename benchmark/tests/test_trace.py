"""The trace reduction on a synthetic trace with known answers, and the
loader on a small trace recorded here."""

import pytest

from benchmark import trace as T


def synthetic():
    # Two epoch programs with an evaluation program and idle time between.
    dev = T.DeviceTrace(
        ops=[
            ("while", 0.0, 4.0),          # container of the two below
            ("fusion.1", 0.0, 1.5),
            ("flash_fwd", 1.5, 2.5),
            ("fusion.eval", 5.0, 1.0),
            ("while", 8.0, 4.0),
            ("fusion.1", 8.0, 1.0),
            ("flash_fwd", 9.0, 3.0),
        ],
        modules=[
            ("jit_epoch(1)", 0.0, 4.0),
            ("jit_evaluate(2)", 5.0, 1.0),
            ("jit_epoch(1)", 8.0, 4.0),
        ],
    )
    host = [
        ("bench:window", -1.0, 14.0),
        ("bench:on_trial_result", 6.5, 1.0),
    ]
    return T.Trace(devices={"/device:TPU:0": dev}, host=host)


def test_union_and_busy():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    tr = synthetic()
    dev = tr.devices["/device:TPU:0"]
    assert T.busy_seconds(dev, (0.0, 12.0)) == pytest.approx(9.0)
    # clipped to a window that cuts the first program in half
    assert T.busy_seconds(dev, (2.0, 12.0)) == pytest.approx(7.0)
    assert T.trace_window(tr) == (0.0, 12.0)
    assert T.annotation_window(tr, "bench:window") == (-1.0, 13.0)


def test_self_times_count_a_container_once():
    dev = synthetic().devices["/device:TPU:0"]
    own = T.self_times(dev.ops)
    assert own["while"] == pytest.approx(0.0)
    assert own["fusion.1"] == pytest.approx(2.5)
    assert own["flash_fwd"] == pytest.approx(5.5)
    assert sum(own.values()) == pytest.approx(9.0)


def test_kernel_seconds_and_module_gaps():
    dev = synthetic().devices["/device:TPU:0"]
    secs, n = T.kernel_seconds(dev, (0.0, 12.0), contains=["flash_fwd"])
    assert (secs, n) == (pytest.approx(5.5), 2)
    # 4 s between the epoch programs, 1 s of it the evaluation program
    assert T.module_gaps(dev, (0.0, 12.0), "jit_epoch") == [pytest.approx(3.0)]


FWD = ('%attention.494 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)S(1)}, '
       'f32[128,1,2048]{2,1,0:T(1,128)}) custom-call(bf16[128,2048,64]{2,1,0:T(8,128)(2,1)} '
       '%bitcast.1, f32[128,1,2048]{2,1,0} %x), custom_call_target="tpu_custom_call"')
BWD = ('%attention.523 = (bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}, '
       'bf16[128,2048,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[128,2048,64]{2,1,0} %b, '
       'f32[128,1,2048]{2,1,0:T(1,128)S(1)} %lse), custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.12 = bf16[16,2048,2048]{2,1,0:T(8,128)(2,1)} fusion(bf16[16,2048,512]{2,1,0} %p), '
          'kind=kOutput, calls=%fused_computation.3')


def test_kernels_are_told_apart_by_what_they_yield_in_the_hlo_text():
    """On the TPU an operation's name is its HLO text (seen on a real
    trace, PR 26): all three flash kernels are '%attention.N' custom
    calls; only the forward yields float32 row statistics."""
    dev = T.DeviceTrace(ops=[(FWD, 0.0, 1.0), (BWD, 1.0, 2.0), (BWD, 3.0, 2.0),
                             (FUSION, 5.0, 4.0)])
    both = dict(contains=["%attention", "tpu_custom_call"])
    assert T.kernel_seconds(dev, (0, 9), yields=["f32["], **both) == (1.0, 1)
    assert T.kernel_seconds(dev, (0, 9), yields_no=["f32["], **both) == (4.0, 2)
    assert T.short_name(FWD) == (
        "%attention custom-call -> (bf16[128,2048,64], f32[128,1,2048])"
    )
    assert T.short_name(FUSION) == "%fusion fusion -> bf16[16,2048,2048]"
    assert T.short_name("plain") == "plain"


def test_idle_gaps_are_named_by_the_innermost_annotation():
    tr = synthetic()
    dev = tr.devices["/device:TPU:0"]
    gaps = T.idle_gaps(dev, (0.0, 12.0))
    assert gaps == [(4.0, 5.0), (6.0, 8.0)]
    named = dict(T.name_gaps(gaps, tr.host))
    assert named["bench:on_trial_result"] == pytest.approx(1.0)
    assert named["bench:window"] == pytest.approx(2.0)
    out = T.breakdown(tr, (0.0, 12.0))
    assert out["device_ops"][0][0] == "flash_fwd"
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    assert path is not None
    tr = T.load(path)
    window = T.annotation_window(tr, "bench:window")
    assert window is not None and window[1] > window[0]
    assert "plane" in T.describe(path)
