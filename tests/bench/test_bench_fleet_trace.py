"""The fleet's spans and phase scopes, reduced from a profiler trace, and
the per-layer metrics that read them."""

import time

import jax
import jax.numpy as jnp
import pytest

import fleet_trace
import manifest
import xplane
from conftest import ROOT

MS = 1e6            # nanoseconds


def synthetic():
    """A window of two read-outs: host spans and device operations (ns)."""
    spans = [
        (0, 100 * MS, "bench.start", {}),
        (0, 90 * MS, "fleet.start", {"readout": 1}),
        (0, 60 * MS, "fleet.audit", {"readout": 1, "nodes": 16,
                                     "programs": 2}),
        (60 * MS, 90 * MS, "fleet.restack", {"readout": 1, "bytes": 800}),
        (100 * MS, 200 * MS, "bench.run", {}),
        (100 * MS, 200 * MS, "fleet.run", {"readout": 1, "rounds": 2}),
        (150 * MS, 180 * MS, "fleet.sync", {"readout": 1, "bytes": 800}),
        (180 * MS, 200 * MS, "fleet.decode", {"readout": 1, "nodes": 16}),
        (200 * MS, 300 * MS, "bench.start", {}),
        (200 * MS, 300 * MS, "fleet.start", {"readout": 2}),
        (200 * MS, 280 * MS, "fleet.audit", {"readout": 2, "nodes": 16,
                                             "programs": 2}),
        (280 * MS, 300 * MS, "fleet.restack", {"readout": 2, "bytes": 800}),
    ]
    ops = [
        (100 * MS, 110 * MS, "fusion.1", "fleet.schedule"),
        (110 * MS, 140 * MS, "vmloop.1", "fleet.execute"),
        (140 * MS, 144 * MS, "concatenate.2", "vmloop.pack"),
        (144 * MS, 145 * MS, "fusion.7", "vmloop.unpack"),
        (145 * MS, 148 * MS, "sort.3", "fleet.router"),
        (148 * MS, 150 * MS, "copy.4", None),
        (250 * MS, 260 * MS, "fusion.9", "fleet.warp"),
    ]
    return ops, spans


def test_reduce_synthetic():
    r = fleet_trace.reduce(*synthetic())
    assert r["window_s"] == pytest.approx(0.3)
    audit = r["span_ms"]["fleet.audit"]
    assert audit["ms"] == pytest.approx(140.0) and audit["n"] == 2
    assert audit["counts"] == {"nodes": 32, "programs": 4}
    assert r["span_ms"]["fleet.run"]["counts"] == {"rounds": 2}
    assert r["phase_s"] == pytest.approx({
        "fleet.schedule": 0.010, "fleet.execute": 0.030,
        "vmloop.pack": 0.004, "vmloop.unpack": 0.001,
        "fleet.router": 0.003, "unscoped": 0.002, "fleet.warp": 0.010,
    })
    assert r["kernel_s"] == pytest.approx(0.030)
    # Idle: [0,100] (audit, restack, then bench.start alone), [150,250]
    # (sync, decode, the second audit) and [260,300] (audit, restack).
    assert r["idle_s"] == pytest.approx(0.240)
    assert r["idle_by_span"] == pytest.approx({
        "fleet.audit": 0.060 + 0.050 + 0.020, "fleet.restack": 0.030 + 0.020,
        "bench.start": 0.010, "fleet.sync": 0.030, "fleet.decode": 0.020,
    })


def test_phases_empty_without_scopes():
    ops, spans = synthetic()
    r = fleet_trace.reduce([op[:3] + (None,) for op in ops], spans)
    assert r["phase_s"] == {} and r["kernel_s"] is None


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A traced CPU window: a jitted function with two phase scopes, under
    ``bench.*`` and ``fleet.*`` host spans."""
    def f(x):
        with jax.named_scope("fleet.schedule"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("fleet.warp"):
            return jnp.sort(y, axis=0)

    f = jax.jit(f)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.run"):
        with jax.profiler.TraceAnnotation("fleet.run", readout=1, rounds=3):
            for _ in range(3):
                f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.read"):
        with jax.profiler.TraceAnnotation("fleet.decode", readout=1):
            time.sleep(0.2)
    jax.profiler.stop_trace()
    return xplane.find_trace(log_dir)


def test_reduce_a_trace_recorded_on_the_cpu(cpu_trace):
    r = fleet_trace.reduce(*fleet_trace.load(cpu_trace, "cpu"))
    assert set(r["phase_s"]) <= {"fleet.schedule", "fleet.warp", "unscoped"}
    assert r["phase_s"]["fleet.schedule"] > 0 and r["phase_s"]["fleet.warp"] > 0
    assert r["span_ms"]["fleet.run"]["counts"] == {"rounds": 3}
    assert r["idle_by_span"]["fleet.decode"] >= 0.19
    # Same window and busy time as the harness's own reduction.
    devices, spans = xplane.load(cpu_trace, 1, "cpu")
    base = xplane.reduce(devices, spans)
    assert r["window_s"] == pytest.approx(base["window_s"])
    assert r["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_a_tpu_run_needs_its_device_plane(cpu_trace):
    with pytest.raises(ValueError, match="no TPU device plane"):
        fleet_trace.load(cpu_trace)
    with pytest.raises(ValueError, match="platform"):
        fleet_trace.load(cpu_trace, "gpu")


NEW = {
    # reader: value on the synthetic window (2 read-outs, 2 rounds)
    "audit_ms": 70.0, "restack_ms": 25.0, "sync_ms": 15.0,
    "decode_ms": 10.0, "audit_nodes": 16.0, "schedule_ms": 5.0,
    "router_ms": 1.5, "warp_ms": 5.0, "pack_ms": 2.5,
}


@pytest.mark.parametrize("base", sorted(NEW))
def test_new_readers(base):
    read = manifest.reader(ROOT, f"{base}.grid").read
    untraced = {"trace": None, "spans": {"start": [0.1, 0.1]}, "rounds": 2}
    assert read(untraced) is None
    ctx = dict(untraced, trace={}, fleet=fleet_trace.reduce(*synthetic()))
    assert read(ctx) == pytest.approx(NEW[base])
    ops, spans = synthetic()
    bare = fleet_trace.reduce([op[:3] + (None,) for op in ops],
                              [sp for sp in spans if sp[2][:6] == "bench."])
    assert read(dict(untraced, trace={}, fleet=bare)) is None


def test_every_new_metric_is_listed_for_both_cells():
    bench = manifest.load(ROOT)
    names = {m["name"] for m in bench["per_layer"]}
    for base in NEW:
        assert {f"{base}.grid", f"{base}.tab10"} <= names
