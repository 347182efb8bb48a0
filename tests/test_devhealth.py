"""Device-link health surface: canary prober state machine with
hysteresis, wedged-runner timeout handling, readiness gating (/readyz +
query fail-fast 503 with Retry-After), dispatch-phase RTT decomposition
(/debug/dispatch + EXPLAIN ANALYZE per-phase actuals), and the
zero-dispatch guarantee when the module is never configured (ISSUE 6
acceptance)."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import devhealth, flightrec
from pilosa_tpu.utils import profile as profile_mod
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture(autouse=True)
def fresh_modules():
    """Every test gets a clean prober slot and flight-recorder ring."""
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)
    yield
    devhealth.stop()
    flightrec.stop_watchdog()
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)
    # analyze queries issued on THIS thread park a profile in the
    # thread-local last-profile slot; drain it or it leaks into the
    # next test file's take_last() assertions
    profile_mod.take_last()


@pytest.fixture
def harness(tmp_path):
    from tests.harness import ServerHarness

    h = ServerHarness(data_dir=str(tmp_path))
    yield h
    h.close()


def _warm_stacked(h):
    """Two-shard data so Count takes the stacked (dispatching) path."""
    h.client.create_index("dh")
    h.client.create_field("dh", "f")
    h.client.query("dh", "Set(3, f=11)")
    h.client.query("dh", f"Set({SHARD_WIDTH + 5}, f=11)")  # 2nd shard
    h.client.query("dh", "Count(Row(f=11))")


def _http(url):
    """(status, headers, body_json) — 4xx/5xx included, not raised."""
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        return e.code, dict(e.headers), json.loads(body) if body else None


# ------------------------------------------------------------ state machine

def test_state_machine_hysteresis_and_recovery():
    """LIVE -> DEGRADED on the 1st failure, -> DOWN on the 3rd, and back
    to LIVE only after live_after consecutive successes."""
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 4:
            raise RuntimeError("device link dead")
        return 0.0

    p = devhealth.configure(canary=flaky, interval=0.01, deadline=2.0,
                            start=False)
    assert p.state == devhealth.LIVE
    states = []
    for _ in range(8):
        p.probe_once()
        states.append(p.state)
    assert states == ["DEGRADED", "DEGRADED", "DOWN", "DOWN", "DOWN",
                      "LIVE", "LIVE", "LIVE"]
    # one lucky probe (state 5) must NOT resurrect a dead link: that is
    # the hysteresis the live_after=2 default buys
    snap = devhealth.snapshot()
    assert [t["to"] for t in snap["transitions"]] == \
        ["DEGRADED", "DOWN", "LIVE"]
    assert snap["probes"]["error"] == 4 and snap["probes"]["ok"] == 4
    # transitions reach the flight recorder and the prometheus gauge
    kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
    assert kinds.count("devhealth.transition") == 3
    _, gauges, _ = global_stats.snapshot()
    assert gauges[("device_link_state", ())] == \
        devhealth.STATE_CODES[devhealth.LIVE]


def test_canary_timeout_and_wedged_runner():
    """A canary that never returns: the probe slot times out at the
    deadline, follow-up slots fail immediately ('still in flight'), and
    probing resumes once the wedged call finally completes."""
    release = threading.Event()

    def slow():
        release.wait(10)
        return 0.0

    p = devhealth.configure(canary=slow, interval=0.01, deadline=0.05,
                            down_after=2, start=False)
    p.probe_once()
    assert p.state == devhealth.DEGRADED
    assert p.last_sample["timeout"]
    assert p.last_sample["error"] == "canary deadline exceeded"
    assert p.last_sample["rtt_seconds"] is None
    p.probe_once()  # runner still wedged: instant failure, no new thread
    assert p.state == devhealth.DOWN
    assert p.last_sample["error"] == "canary still in flight"
    assert devhealth.is_down()
    release.set()
    deadline = time.time() + 5
    while p._runner.busy and time.time() < deadline:
        time.sleep(0.01)
    p.probe_once()
    p.probe_once()
    assert p.state == devhealth.LIVE
    assert p.probes_timeout == 2 and p.probes_ok == 2


def test_sample_splits_lock_wait_from_pure_rtt():
    def canary():
        time.sleep(0.02)
        return 0.015  # of which 15ms was spent waiting on the lock

    p = devhealth.configure(canary=canary, deadline=1.0, start=False)
    p.probe_once()
    s = p.last_sample
    assert s["ok"] and not s["timeout"]
    assert s["rtt_seconds"] >= 0.02
    assert s["lock_wait_seconds"] == pytest.approx(0.015)
    assert s["pure_rtt_seconds"] == pytest.approx(
        s["rtt_seconds"] - 0.015, abs=1e-5)


def test_started_prober_probes_continuously():
    p = devhealth.configure(canary=lambda: 0.0, interval=0.01,
                            deadline=1.0)
    deadline = time.time() + 5
    while p.probes_total < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert p.probes_total >= 3
    assert p.state == devhealth.LIVE
    s = devhealth.summary()
    assert s["probes"]["ok"] >= 3
    assert s["last"]["rtt_seconds"] >= 0


# ------------------------------------------------------- disabled guarantee

def test_disabled_module_is_inert_and_dispatch_free():
    """Never configured: DISABLED (deliberately ready), empty snapshot,
    and the canary is NEVER invoked — zero device dispatches."""
    assert devhealth.state() == devhealth.DISABLED
    assert not devhealth.is_down()
    assert devhealth.summary() == {"state": devhealth.DISABLED}
    snap = devhealth.snapshot()
    assert snap["ring"] == [] and snap["transitions"] == []
    assert devhealth.get_prober() is None
    calls = []
    devhealth.configure(canary=lambda: calls.append(1) or 0.0,
                        start=False)
    time.sleep(0.05)
    assert calls == []  # built but not started: still no canary calls
    devhealth.stop()
    assert devhealth.state() == devhealth.DISABLED
    _, gauges, _ = global_stats.snapshot()
    assert gauges[("device_link_state", ())] == \
        devhealth.STATE_CODES[devhealth.DISABLED]


# -------------------------------------------------------- readiness gating

def test_readyz_flips_and_query_fails_fast(harness):
    from pilosa_tpu.server.api import ServiceUnavailableError

    harness.client.create_index("dh")
    harness.client.create_field("dh", "f")
    harness.client.query("dh", "Set(3, f=1)")

    code, _, body = _http(harness.address + "/readyz")
    assert code == 200 and body["device_link"] == devhealth.DISABLED

    mode = {"ok": False}

    def canary():
        if not mode["ok"]:
            raise RuntimeError("device link dead")
        return 0.0

    p = devhealth.configure(canary=canary, interval=0.5, deadline=1.0,
                            start=False)
    for _ in range(3):
        p.probe_once()
    assert devhealth.state() == devhealth.DOWN

    code, headers, _ = _http(harness.address + "/readyz")
    assert code == 503
    assert headers.get("Retry-After") == "1"
    # liveness is NOT readiness: the process itself is fine
    code, _, _ = _http(harness.address + "/healthz")
    assert code == 200

    # query fail-fast: 503 + Retry-After without touching the device
    with pytest.raises(ServiceUnavailableError) as ei:
        harness.api.query("dh", "Count(Row(f=1))")
    assert ei.value.status == 503
    assert ei.value.headers["Retry-After"] == "1"
    kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
    assert "query.rejected" in kinds

    # recovery: live_after consecutive successes reopen the gate
    mode["ok"] = True
    p.probe_once()
    p.probe_once()
    assert devhealth.state() == devhealth.LIVE
    code, _, body = _http(harness.address + "/readyz")
    assert code == 200 and body["device_link"] == devhealth.LIVE
    assert harness.api.query("dh", "Count(Row(f=1))")


def test_status_observability_carries_device_link(harness):
    p = devhealth.configure(canary=lambda: 0.0, start=False)
    p.probe_once()
    status = harness.client.status()
    link = status["observability"]["local"]["device_link"]
    assert link["state"] == devhealth.LIVE
    assert link["probes"]["ok"] == 1


# ------------------------------------------------------- /debug endpoints

def test_debug_device_endpoint(harness):
    snap = harness.client.debug_device()
    assert snap["state"] == devhealth.DISABLED
    p = devhealth.configure(canary=lambda: 0.0, start=False)
    for _ in range(5):
        p.probe_once()
    snap = harness.client.debug_device()
    assert snap["state"] == devhealth.LIVE
    assert len(snap["ring"]) == 5
    assert all(s["ok"] for s in snap["ring"])
    assert snap["thresholds"] == {
        "degraded_after": 1, "down_after": 3, "live_after": 2}
    limited = harness.client.debug_device(limit=2)
    assert len(limited["ring"]) == 2


def test_debug_dispatch_phase_decomposition(harness):
    """Phase seconds (minus lock_wait) sum to the family's kernel wall —
    exact by construction; rel=5% is the acceptance bound."""
    _warm_stacked(harness)
    snap = harness.client.debug_dispatch()
    assert "count" in snap["phases"]
    fam = snap["phases"]["count"]
    assert "compile" in fam  # first Count call compiled
    assert "sync" in fam and "lock_wait" in fam
    wall = harness.api.executor._stacked.kernel_profile()["count"]["seconds"]
    total = sum(p["seconds"] for name, p in fam.items()
                if name != "lock_wait")
    assert total == pytest.approx(wall, rel=0.05)


def test_explain_analyze_carries_phase_attribution(harness):
    from pilosa_tpu.exec import plan as plan_mod
    from pilosa_tpu.exec.executor import ExecOptions

    _warm_stacked(harness)
    harness.api.query("dh", "Count(Row(f=11))",
                      options=ExecOptions(explain="analyze"))
    env = plan_mod.take_last()
    actual = env["calls"][0]["actual"]
    ph = actual.get("phase_seconds")
    assert ph, "analyze grafted no per-phase attribution"
    assert "sync" in ph or "dispatch_ack" in ph
    assert all(v >= 0 for v in ph.values())
    # the decomposition nets out against the actual kernel wall
    assert sum(v for k, v in ph.items() if k != "lock_wait") == \
        pytest.approx(actual["kernel_wall_seconds"], rel=0.05, abs=1e-4)


# ------------------------------------------------------ flightrec satellite

def test_watchdog_stall_includes_device_link_state():
    p = devhealth.configure(canary=lambda: 0.0, start=False)
    p.probe_once()
    wd = flightrec.Watchdog(deadline=0.01)
    token = wd.begin_op("wedged")
    time.sleep(0.03)
    wd.check()
    wd.end_op(token)
    evt = [e for e in flightrec.snapshot()["events"]
           if e["kind"] == "watchdog.stall"][-1]
    assert evt["tags"]["device_link_state"] == devhealth.LIVE
