"""Device telemetry & capacity attribution (ISSUE 15).

The acceptance arc: a booted CPU-only node serves GET /device and
GET /capacity, the capacity model names the binding constraint with
per-resource headroom (today: host_pump — BENCH_r06's wall, stated by
the node itself with evidence), `what_if` substitution changes the
named constraint on a synthetic input, and on the kernel-stubbed
multi-device rig per-device busy/queue/transfer attribution plus the
`device.hbm_pressure` + `device.utilization_collapse` alerts fire and
resolve with evidence. The <=2% plane-overhead bound is gated by
`bench.py --quick device` (subprocess smoke at the bottom).

Simulated time (TestClock) everywhere the plane allows it; the booted
node and the bench smoke are real time.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from corda_tpu.client.webserver import NodeWebServer
from corda_tpu.crypto import schemes
from corda_tpu.crypto.batch_verifier import (
    TpuBatchVerifier,
    VerificationRequest,
)
from corda_tpu.node.services import TestClock
from corda_tpu.utils import device_telemetry as dlib
from corda_tpu.utils import health as hlib
from corda_tpu.utils import perf as plib
from corda_tpu.utils.metrics import MetricRegistry


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers["Content-Type"], resp.read()


def _get_json(url, timeout=10):
    status, _, body = _get(url, timeout)
    return status, json.loads(body)


class FakeDevice:
    """What a jax device row looks like to the sampler — with a
    scripted, mutable memory-stats feed (the hbm_pressure arc)."""

    def __init__(self, device_id, platform="tpu", kind="fake-v5e",
                 limit=16 * 1024**3, in_use=0):
        self.id = device_id
        self.platform = platform
        self.device_kind = kind
        self.limit = limit
        self.in_use = in_use

    def memory_stats(self):
        if self.limit is None:
            return None          # the CPU-backend degradation
        return {
            "bytes_in_use": self.in_use,
            "peak_bytes_in_use": self.in_use,
            "bytes_limit": self.limit,
        }


def _p256_requests(n: int):
    kp = schemes.generate_keypair(
        schemes.ECDSA_SECP256R1_SHA256, seed=23
    )
    msg = b"device-telemetry"
    sig = kp.private.sign(msg)
    return [VerificationRequest(kp.public, sig, msg)] * n


def _stub_kernels(monkeypatch):
    monkeypatch.setattr(
        TpuBatchVerifier,
        "_kernel",
        lambda self, scheme_id, batch: (
            lambda **staged: np.ones(batch, dtype=bool)
        ),
    )


# ---------------------------------------------------------------------------
# capacity model (pure units)


SYNTH = {
    # today's CPU-container shape: the host pump is the ~41.5k/s wall
    # (BENCH_r06) while the chip and the link idle far above it
    "pump_seconds_per_tx": 24e-6,
    "commit_seconds_per_tx": 4e-6,
    "device_seconds_per_tx": 2e-6,
    "device_count": 1,
    "transfer_bytes_per_tx": 160.0,
    "transfer_bytes_per_sec": 50e6,
    "current_per_sec": 30_000.0,
}


def test_capacity_model_names_host_pump_with_headroom():
    out = dlib.capacity_model(dict(SYNTH))
    assert out["binding_constraint"] == "host_pump"
    assert out["predicted_ceiling_per_sec"] == pytest.approx(
        1e6 / 24, rel=0.01
    )
    # every bounded resource carries a headroom fraction; the idle
    # ones are far from their ceilings
    rows = out["resources"]
    assert rows["device_compute"]["headroom_fraction"] > 0.9
    assert rows["transfer"]["headroom_fraction"] > 0.9
    assert 0 <= rows["host_pump"]["headroom_fraction"] < 0.35
    # the operator sentence states the constraint with evidence
    assert "host_pump binds the notary line" in out["sentence"]
    assert "24.0us/tx" in out["sentence"]


def test_what_if_substitution_changes_the_named_constraint():
    base = dlib.capacity_model(dict(SYNTH))
    assert base["binding_constraint"] == "host_pump"
    # the GIL-escape plan: 8 per-shard pump processes — host_pump and
    # commit_plane scale, and the model names the NEXT wall
    plan = dlib.capacity_model(
        dict(SYNTH), dlib.parse_what_if("shards:8")
    )
    assert plan["binding_constraint"] != "host_pump"
    assert (
        plan["predicted_ceiling_per_sec"]
        > base["predicted_ceiling_per_sec"]
    )
    # raw-input substitution flips toward any chosen resource
    slow_link = dlib.capacity_model(
        dict(SYNTH),
        dlib.parse_what_if("transfer_bytes_per_sec:1000000"),
    )
    assert slow_link["binding_constraint"] == "transfer"
    slow_chip = dlib.capacity_model(
        dict(SYNTH), dlib.parse_what_if("device_us_per_tx:2000")
    )
    assert slow_chip["binding_constraint"] == "device_compute"
    # commit_plane binds when the measured pump-hot lock holds exceed
    # the commit timer (the PR 14 split-report feed)
    held = dict(SYNTH, lock_hold_seconds_per_tx=60e-6)
    locky = dlib.capacity_model(held)
    assert locky["binding_constraint"] == "commit_plane"
    assert "pump-hot lock holds" in locky["sentence"]


def test_capacity_model_unmeasured_resources_are_unbounded():
    # a CPU-only rig: no device dispatches, no timed transfers — the
    # model must resolve (and name host_pump), never guess a ceiling
    out = dlib.capacity_model({
        "pump_seconds_per_tx": 24e-6,
        "commit_seconds_per_tx": 4e-6,
    })
    assert out["binding_constraint"] == "host_pump"
    assert out["resources"]["device_compute"]["ceiling_per_sec"] is None
    assert out["resources"]["transfer"]["ceiling_per_sec"] is None
    # nothing measured at all: no constraint, no crash
    empty = dlib.capacity_model({})
    assert empty["binding_constraint"] is None
    assert empty["sentence"] is None


def test_parse_what_if_rejects_unknown_knobs_and_bad_values():
    assert dlib.parse_what_if("shards:8,devices:4") == {
        "shards": 8.0, "devices": 4.0,
    }
    with pytest.raises(ValueError, match="unknown what_if knob"):
        dlib.parse_what_if("warp:9")
    with pytest.raises(ValueError, match="bad what_if value"):
        dlib.parse_what_if("shards:many")
    with pytest.raises(ValueError, match="must be positive"):
        dlib.parse_what_if("shards:0")


# ---------------------------------------------------------------------------
# sampler


def test_sampler_memory_stats_absent_not_fatal():
    # a fake CPU-backend device (memory_stats -> None) and a device
    # with no memory_stats method at all both sample as hbm=null
    class Bare:
        id, platform, device_kind = 7, "cpu", "cpu"

    sampler = dlib.DeviceSampler(
        lambda: [FakeDevice(0, platform="cpu", limit=None), Bare()]
    )
    rows = sampler.sample(census=False)
    assert [r["id"] for r in rows] == [0, 7]
    assert all(r["hbm"] is None for r in rows)

    # the real backend (virtual CPU mesh in this suite) samples too
    real = dlib.DeviceSampler().sample(census=False)
    assert len(real) >= 1
    assert all("hbm" in r for r in real)


def test_sampler_live_buffer_census_counts_resident_arrays():
    import jax.numpy as jnp

    pin = jnp.ones((128,), jnp.float32)     # keep one array resident
    buffers = dlib.DeviceSampler().live_buffers()
    assert buffers, "no live arrays visible to the census"
    total = sum(row["count"] for row in buffers.values())
    assert total >= 1
    assert all(row["bytes"] >= 0 for row in buffers.values())
    del pin


# ---------------------------------------------------------------------------
# per-device dispatch attribution (the verify seam)


def test_unpinned_dispatch_times_the_device_put_transfer(monkeypatch):
    """Satellite: the default-device dispatch path now times its
    device_put — transfer bytes no longer ride with ZERO transfer
    seconds, so a single-device rig's transfer_bytes_per_sec is a
    real rate instead of a lie."""
    _stub_kernels(monkeypatch)
    acct = plib.KernelAccounting()
    devacct = dlib.DeviceAccounting()
    dlib.set_device_accounting(devacct)
    try:
        v = TpuBatchVerifier(batch_sizes=(4,), perf=acct)
        assert all(v.verify_batch(_p256_requests(3)))
    finally:
        dlib.set_device_accounting(None)
    row = acct.snapshot()["keys"][
        f"scheme{schemes.ECDSA_SECP256R1_SHA256}/batch4"
    ]
    assert row["transfer_bytes"] > 0
    assert row["transfer_seconds"] > 0          # the satellite's point
    assert row["transfer_bytes_per_sec"] is not None
    # and the same transfer landed on the DEVICE ledger, keyed by the
    # default device's id
    snap = devacct.snapshot()
    assert snap["totals"]["transfer_bytes"] == row["transfer_bytes"]
    assert snap["totals"]["transfer_seconds"] > 0


def test_dispatch_records_real_and_padded_rows(monkeypatch):
    """A launch records the real rows it verifies and the padded rows
    the ladder computes for them (its batch), side by side."""
    _stub_kernels(monkeypatch)
    devacct = dlib.DeviceAccounting()
    dlib.set_device_accounting(devacct)
    try:
        v = TpuBatchVerifier(batch_sizes=(32,))
        assert all(v.verify_batch(_p256_requests(5)))
    finally:
        dlib.set_device_accounting(None)
    totals = devacct.snapshot()["totals"]
    assert (totals["dispatches"], totals["requests"], totals["rows"]) == (
        1, 5, 32)
    devacct.record_dispatch(0, 7, 0.001)      # rows default to the real
    assert devacct.snapshot()["totals"]["rows"] == 39


def test_multi_device_dispatch_attribution(monkeypatch):
    """The kernel-stubbed multi-device rig: two device-pinned
    verifiers (the sharded notary's per-device path) attribute busy
    wall, request counts, queue wait and transfer to THEIR device
    rows, and the plane windows them into per-device busy fractions
    and mapped queue depths."""
    import jax

    devices = jax.devices()
    assert len(devices) >= 2, "conftest forces an 8-device CPU mesh"
    _stub_kernels(monkeypatch)
    devacct = dlib.DeviceAccounting()
    dlib.set_device_accounting(devacct)
    try:
        v0 = TpuBatchVerifier(batch_sizes=(4,), device=devices[0])
        v1 = TpuBatchVerifier(batch_sizes=(4,), device=devices[1])
        assert all(v0.verify_batch(_p256_requests(3)))
        for _ in range(3):
            assert all(v1.verify_batch(_p256_requests(4)))
    finally:
        dlib.set_device_accounting(None)
    snap = devacct.snapshot()["devices"]
    d0, d1 = devices[0].id, devices[1].id
    assert snap[d0]["dispatches"] == 1 and snap[d0]["requests"] == 3
    assert snap[d1]["dispatches"] == 3 and snap[d1]["requests"] == 12
    for did in (d0, d1):
        assert snap[did]["busy_seconds"] > 0
        assert snap[did]["queue_wait_seconds"] > 0
        assert snap[did]["transfer_bytes"] > 0
        assert snap[did]["transfer_seconds"] > 0

    # the plane windows the ledger: per-device busy fraction, and
    # queue depths mapped by shard->device pinning
    clock = TestClock()
    plane = dlib.DevicePlane(
        clock=clock,
        policy=dlib.DevicePolicy(
            sample_gap_micros=0, live_buffer_census=False
        ),
        sampler=dlib.DeviceSampler(lambda: list(devices[:2])),
        accounting=devacct,
    )
    depths = {d0: 5, d1: 11}
    plane.attach_queues(
        [lambda: depths[d0], lambda: depths[d1]], [d0, d1]
    )
    plane.tick()
    clock.advance(1_000_000)
    devacct.record_dispatch(d1, 4, 0.25, 0.001)   # busy inside window
    plane.tick()
    assert plane.queue_depth(d0) == 5
    assert plane.queue_depth(d1) == 11
    assert plane.backlog() == 16
    body = plane.snapshot()
    rows = {r["id"]: r for r in body["devices"]}
    assert rows[d1]["busy_fraction"] == pytest.approx(0.25, rel=0.05)
    assert rows[d1]["busy_fraction"] > rows[d0]["busy_fraction"]
    assert rows[d1]["dispatch_totals"]["requests"] == 16


# ---------------------------------------------------------------------------
# alert rules (simulated clock)


def _plane_with_monitor(feed, queue_fn=None):
    clock = TestClock()
    metrics = MetricRegistry()
    plane = dlib.DevicePlane(
        clock=clock,
        metrics=metrics,
        policy=dlib.DevicePolicy(
            sample_gap_micros=0, live_buffer_census=False
        ),
        sampler=dlib.DeviceSampler(feed),
        install_default_accounting=False,
    )
    if queue_fn is not None:
        plane.attach_queues([queue_fn], [None])
    monitor = hlib.HealthMonitor(clock=clock, metrics=metrics)
    monitor.watch_device(plane)
    return clock, plane, monitor


def _walk(clock, plane, monitor, rounds=4, step=1_000_000):
    for _ in range(rounds):
        plane.tick()
        monitor.tick()
        clock.advance(step)


def test_hbm_pressure_fires_on_sustained_occupancy_then_resolves():
    dev = FakeDevice(0, in_use=int(0.5 * 16 * 1024**3))
    clock, plane, monitor = _plane_with_monitor(lambda: [dev])
    _walk(clock, plane, monitor)
    alerts = monitor.snapshot()["alerts"]
    assert alerts["device.hbm_pressure"]["state"] in (
        "inactive", "resolved",
    )

    # sustained 96% occupancy: pending -> firing past the hold, with
    # the pressured device named in the detail
    dev.in_use = int(0.96 * dev.limit)
    _walk(clock, plane, monitor, rounds=5)
    alert = monitor.snapshot()["alerts"]["device.hbm_pressure"]
    assert alert["state"] == "firing"
    assert alert["detail"]["worst"]["device"] == 0
    assert alert["detail"]["worst"]["utilization"] >= 0.92

    # a one-tick spike back under threshold is hysteresis territory;
    # sustained relief resolves
    dev.in_use = int(0.3 * dev.limit)
    _walk(clock, plane, monitor, rounds=5)
    alert = monitor.snapshot()["alerts"]["device.hbm_pressure"]
    assert alert["state"] == "resolved"
    assert alert["fire_count"] == 1


def test_utilization_collapse_fires_when_pump_starves_the_chip():
    backlog = {"n": 0}
    clock, plane, monitor = _plane_with_monitor(
        lambda: [FakeDevice(0)], queue_fn=lambda: backlog["n"]
    )
    # a busy, drained plane: dispatches land every round, backlog flat
    for _ in range(4):
        plane.accounting.record_dispatch(0, 64, 0.5, 0.001)
        _walk(clock, plane, monitor, rounds=1)
    assert (
        monitor.snapshot()["alerts"]["device.utilization_collapse"]
        ["state"] == "inactive"
    )
    # the pump stalls: busy collapses while the backlog grows — the
    # "pump starved the chip" signature
    for _ in range(40):
        backlog["n"] += 64
        _walk(clock, plane, monitor, rounds=1)
    alert = monitor.snapshot()["alerts"]["device.utilization_collapse"]
    assert alert["state"] == "firing", alert
    assert alert["detail"]["backlog_growth_in_window"] > 0
    assert alert["detail"]["busy_fraction_max"] < 0.10
    # recovery: dispatches resume and the backlog drains
    for _ in range(8):
        backlog["n"] = max(0, backlog["n"] - 512)
        plane.accounting.record_dispatch(0, 64, 0.5, 0.001)
        _walk(clock, plane, monitor, rounds=1)
    alert = monitor.snapshot()["alerts"]["device.utilization_collapse"]
    assert alert["state"] == "resolved"


def test_fallback_bridge_fires_with_device_evidence():
    degraded = {"on": False}
    clock, plane, monitor = _plane_with_monitor(
        lambda: [FakeDevice(3, in_use=1024)]
    )
    plane.watch_fallback(
        lambda: degraded["on"],
        lambda: {"error": "DeviceFaultError: injected"},
    )
    _walk(clock, plane, monitor, rounds=1)
    assert (
        monitor.snapshot()["alerts"]["device.fallback_active"]["state"]
        == "inactive"
    )
    degraded["on"] = True
    _walk(clock, plane, monitor, rounds=1)
    alert = monitor.snapshot()["alerts"]["device.fallback_active"]
    assert alert["state"] == "firing"      # zero hold: follows the flag
    assert alert["detail"]["degraded_evidence"]["error"].startswith(
        "DeviceFaultError"
    )
    assert alert["detail"]["devices"][0]["id"] == 3
    degraded["on"] = False
    _walk(clock, plane, monitor, rounds=1)
    assert (
        monitor.snapshot()["alerts"]["device.fallback_active"]["state"]
        == "resolved"
    )


# ---------------------------------------------------------------------------
# fleet: the device_fault chaos events assert the telemetry story


def test_fleet_device_fault_tells_the_telemetry_story():
    from corda_tpu.testing import fleet as fl

    scen = fl.FleetScenario(
        clients=32,
        phases=(fl.Phase("steady", rounds=30, offered_per_round=2),),
    )
    sim = fl.FleetSim(
        scen, "batching",
        chaos=(fl.device_fault(at=0.15, heal_at=0.3, flushes=2),),
    )
    rep = sim.run()
    assert rep.device_faults == 2
    # the plane saw the fallback arc and reads clean at the end
    assert rep.device_telemetry is not None
    assert rep.device_telemetry["fallback_active"] is False
    alert = rep.monitors[sim.members[0].name].snapshot()["alerts"][
        "device.fallback_active"
    ]
    assert alert["fire_count"] >= 1 and alert["state"] == "resolved"
    # the checker reconciles the telemetry story with injected reality
    fl.InvariantChecker(rep).check_health_story()


# ---------------------------------------------------------------------------
# the booted-node acceptance + endpoint wiring


def test_node_boots_device_plane_and_serves_endpoints(tmp_path):
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    node = Node(
        NodeConfig(
            name="DeviceNode", base_dir=str(tmp_path / "n"),
            notary="batching", use_tls=False,
            verifier_backend="cpu", web_port=0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        assert node.device_plane is not None
        base = f"http://127.0.0.1:{node.web.port}"
        # drive the canary through real flushes so the phase timers
        # (the capacity model's host-pump input) populate
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            node.pump()
            if node.health.canary.completed >= 1:
                break
            time.sleep(0.01)
        assert node.health.canary.completed >= 1
        for _ in range(3):
            node.pump()
            time.sleep(0.02)

        # GET /device: per-device rows; the CPU backend degrades
        # honestly (hbm null, never a failure)
        status, dev = _get_json(base + "/device")
        assert status == 200
        assert dev["devices"], "no devices sampled"
        for row in dev["devices"]:
            assert row["platform"] == "cpu"
            assert row["hbm"] is None          # absent-not-fatal
        assert dev["fallback_active"] is False

        # GET /capacity: the model resolves on the measured flush
        # phases and names host_pump — BENCH_r06's wall, stated by
        # the node itself with evidence
        status, cap = _get_json(base + "/capacity")
        assert status == 200
        assert cap["binding_constraint"] == "host_pump"
        assert "host_pump binds the notary line" in cap["sentence"]
        assert "us/tx across the flush phases" in cap["sentence"]
        host = cap["resources"]["host_pump"]
        assert host["ceiling_per_sec"] > 0
        assert host["headroom_fraction"] is not None
        assert host["headroom_fraction"] > 0     # nonzero headroom
        # unmeasured resources are unbounded, not guessed
        assert cap["resources"]["device_compute"]["ceiling_per_sec"] \
            is None

        # ?what_if= substitution round-trips through the endpoint
        status, plan = _get_json(
            base + "/capacity?what_if=pump_us_per_tx:10,"
            "transfer_bytes_per_sec:1000000,transfer_bytes_per_tx:1000"
        )
        assert status == 200
        assert plan["what_if"]["pump_us_per_tx"] == 10.0
        assert plan["binding_constraint"] == "transfer"
        # a bad knob is a 400 naming the knobs, not a 500
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                base + "/capacity?what_if=warp:9", timeout=10
            )
        assert exc.value.code == 400
        assert "unknown what_if knob" in json.loads(exc.value.read())[
            "error"
        ]

        # Device.* gauges on the scrape surface
        _, _, metrics_text = _get(base + "/metrics")
        assert b"Device_Count" in metrics_text
        assert b"Device_0_BusyFraction" in metrics_text
        assert b"Device_0_QueueDepth" in metrics_text
        assert b"Device_0_HbmUtilization" in metrics_text

        # the shared ?ts=1 echo on both new endpoints
        _, dev_ts = _get_json(base + "/device?ts=1")
        _, cap_ts = _get_json(base + "/capacity?ts=1")
        assert isinstance(dev_ts["ts_micros"], int)
        assert isinstance(cap_ts["ts_micros"], int)
        _, plain = _get_json(base + "/device")
        assert "ts_micros" not in plain

        # endpoint-index rows, enabled
        _, index = _get_json(base + "/")
        paths = {e["path"]: e for e in index["endpoints"]}
        assert paths["/device"]["enabled"] is True
        assert paths["/capacity"]["enabled"] is True
        assert "what_if" in paths["/capacity"]["description"]
    finally:
        node.stop()


def test_webserver_device_404_when_not_wired():
    web = NodeWebServer(
        client=object(), pump=lambda: None, metrics=MetricRegistry()
    ).start()
    try:
        base = f"http://127.0.0.1:{web.port}"
        for path in ("/device", "/capacity"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + path, timeout=10)
            assert exc.value.code == 404
            assert "error" in json.loads(exc.value.read())
        status, index = _get_json(base + "/")
        paths = {e["path"]: e for e in index["endpoints"]}
        assert paths["/device"]["enabled"] is False
        assert paths["/capacity"]["enabled"] is False
    finally:
        web.stop()


def test_config_gates_the_plane_and_roundtrips(tmp_path):
    from corda_tpu.node.config import (
        NodeConfig, load_config, write_config,
    )

    cfg = NodeConfig(
        name="A", base_dir=str(tmp_path),
        device_telemetry_enabled=False,
    )
    path = str(tmp_path / "node.toml")
    write_config(cfg, path)
    loaded = load_config(path)
    assert loaded.device_telemetry_enabled is False
    # default on: the knob is omitted from the emitted file
    write_config(NodeConfig(name="A", base_dir=str(tmp_path)), path)
    assert "device_telemetry_enabled" not in open(path).read()
    assert load_config(path).device_telemetry_enabled is True


def test_disabled_plane_serves_404_on_a_booted_node(tmp_path):
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    node = Node(
        NodeConfig(
            name="NoDevNode", base_dir=str(tmp_path / "n"),
            notary="batching", use_tls=False,
            verifier_backend="cpu", web_port=0,
            device_telemetry_enabled=False,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        assert node.device_plane is None
        base = f"http://127.0.0.1:{node.web.port}"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/device", timeout=10)
        assert exc.value.code == 404
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# CI smoke: the bench plumbing itself (plane overhead + capacity proof)


def test_bench_quick_device_bounds_overhead_and_names_host_pump():
    """`bench.py --quick device` must run under JAX_PLATFORMS=cpu and
    gate the plane's per-flush tick at <=2% of the notary flush wall,
    with the capacity model naming host_pump in the same record."""
    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "device"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "device_plane_overhead"
    assert rec["quick"] is True
    assert rec["value"] <= 0.02
    assert rec["device_plane_overhead_ok"] is True
    assert rec["capacity_names_host_pump"] is True
    assert rec["binding_constraint"] == "host_pump"
    assert set(rec["gate_required_true"]) == {
        "device_plane_overhead_ok", "capacity_names_host_pump",
    }
    assert rec["headroom_fractions"]["host_pump"] is not None
