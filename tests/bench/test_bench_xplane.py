"""The trace reduction: busy, idle and kernel time from a profiler trace."""

import time

import jax
import jax.numpy as jnp
import pytest

import xplane


def test_reduce_synthetic_intervals():
    ops = [(0, 10, ["a"]), (5, 20, ["x", "vmloop.3"]), (40, 50, ["b"]),
           (55, 70, ["vmloop"])]
    spans = [(0, 30, "start"), (30, 60, "run")]
    r = xplane.reduce([ops], spans)
    assert r["window_s"] == pytest.approx(60e-9)
    assert r["busy_s"] == pytest.approx(35e-9)        # [0,20] [40,50] [55,60]
    assert r["kernel_s"]["vmloop"] == pytest.approx(20e-9)
    assert r["idle_gaps"] == [["start", pytest.approx(20e-9)],
                              ["run", pytest.approx(5e-9)]]
    assert r["device_ops"][0] == ["x", pytest.approx(15e-9)]


def test_union_merges_and_clips():
    assert xplane.union([(5, 9), (0, 3), (2, 6), (20, 30)], 1, 25) == [
        (1, 9), (20, 25)]


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.run"):
        for _ in range(3):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.read"):
        time.sleep(0.2)
    jax.profiler.stop_trace()
    devices, spans = xplane.load(xplane.find_trace(tmp_path), 1, "cpu")
    assert [s[2] for s in spans] == ["run", "read"]
    r = xplane.reduce(devices, spans, kernels=("dot",))
    assert r["window_s"] >= 0.2
    assert 0 < r["kernel_s"]["dot"] <= r["busy_s"] < r["window_s"] - 0.19
    name, gap = r["idle_gaps"][0]
    assert name == "read" and gap >= 0.19


def test_a_tpu_run_without_a_device_plane_raises(tmp_path):
    f = jax.jit(lambda x: x * 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.run"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_trace(tmp_path)
    with pytest.raises(ValueError, match="TPU device plane"):
        xplane.load(path)
    with pytest.raises(ValueError, match="platform"):
        xplane.load(path, 1, "gpu")
