"""graftfleet: the multi-host serving tier (serve/fleet/).

What must hold (docs/SERVING.md "Fleet tier"):

- LeaseCoordinator: equal-share availability-capped grants, sum of live
  fractions per tenant NEVER exceeds 1.0 (OverCommitError is the only
  over-admission path — falsified directly), membership changes bump the
  epoch, expired slices are reclaimed and counted.
- LeaseClient: bounded staleness — a lease stops being USED at
  USE_FRACTION·TTL, strictly before the coordinator reclaims it at the
  full TTL; a partitioned host sheds (reason "lease") instead of serving
  on stale slices.
- kill -9 one replica: its slices expire and redistribute to survivors
  within the TTL bound, and the SAMPLED sum of usable fractions never
  exceeds 1.0 through the hand-off — over-admission pinned impossible.
- FleetRouter: deterministic smooth-WRR spread, drain-by-cause
  ("swap_in_flight" drains, "shedding" stays routable), typed
  HostLostError → sibling reroute → NoReplicaError when nobody is left,
  session affinity with monotone re-pin only while idle.
- WaveController: wave-ordered drain → idle → swap → undrain, lost
  replicas skipped; engine-backed waves keep compile_count flat.
- run_fleet_scenario: all three fleet drills emit schema-valid records
  with zero silent drops and zero over-ceiling window samples; the
  serve-bench --fleet-scenario CLI path refuses bad grammar with exit 2.
"""

import json
import threading
import time

import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.analysis.bench_schema import validate_record
from distributed_sigmoid_loss_tpu.serve.admission import ShedError, TenantPolicy
from distributed_sigmoid_loss_tpu.serve.fleet import (
    USE_FRACTION,
    FleetRouter,
    LeaseClient,
    LeaseCoordinator,
    LeasedAdmission,
    NoReplicaError,
    OverCommitError,
    ReplicaHandle,
    WaveController,
    build_fleet,
    run_fleet_scenario,
)
from distributed_sigmoid_loss_tpu.serve.siege import HostLostError


def _wait_until(cond, timeout_s=5.0, poll_s=0.005):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return cond()


# ---------------------------------------------------------------------------
# LeaseCoordinator: the grant-table invariant
# ---------------------------------------------------------------------------


def test_coordinator_equal_shares_converge_and_epoch_tracks_membership():
    coord = LeaseCoordinator({"gold": 100.0}, ttl_s=5.0)
    first = coord.acquire("h0")
    assert first["gold"].fraction == pytest.approx(1.0)  # sole member
    epoch_solo = coord.stats()["lease_epoch"]

    # h1 joins: target drops to 1/2, but h0 still holds 1.0 live — the
    # availability cap grants h1 NOTHING rather than overshooting. The
    # next renew round converges both to the equal share.
    joined = coord.acquire("h1")
    assert joined["gold"].fraction == pytest.approx(0.0)
    assert coord.stats()["lease_epoch"] > epoch_solo  # membership bump
    assert coord.acquire("h0")["gold"].fraction == pytest.approx(0.5)
    assert coord.acquire("h1")["gold"].fraction == pytest.approx(0.5)
    assert coord.granted_fraction("gold") == pytest.approx(1.0)


def test_grant_overcommit_is_refused_never_recorded():
    """Falsification: the only way past 1.0 is the typed raise."""
    coord = LeaseCoordinator({"t": 10.0}, ttl_s=5.0)
    coord.grant("t", "a", 0.7)
    with pytest.raises(OverCommitError):
        coord.grant("t", "b", 0.4)
    # The refused grant left no trace; exactly-1.0 still lands.
    assert coord.granted_fraction("t") == pytest.approx(0.7)
    coord.grant("t", "b", 0.3)
    assert coord.granted_fraction("t") == pytest.approx(1.0)
    # Re-granting the SAME host replaces its slice (no double count).
    coord.grant("t", "a", 0.7)
    assert coord.granted_fraction("t") == pytest.approx(1.0)


def test_lease_usable_window_ends_strictly_before_reclaim():
    """The safety asymmetry itself: usable_until < expires_at, and the
    client stops USING the slice while the coordinator still counts it
    live — the gap in which a dead host's slice is dark on both sides."""
    coord = LeaseCoordinator({"t": 10.0}, ttl_s=1.0)
    lease = coord.grant("t", "h", 1.0)
    assert lease.usable_until() == pytest.approx(
        lease.granted_at + USE_FRACTION * coord.ttl_s
    )
    assert lease.usable_until() < lease.expires_at()

    client = LeaseClient(coord, "h2", renew_interval_s=60.0)
    client.renew_once()
    assert client.fraction("t") == pytest.approx(0.0)  # h holds it all
    # h never renews: at USE_FRACTION·TTL its fraction goes dark...
    assert _wait_until(
        lambda: coord.granted_fraction("t") == 0.0, timeout_s=3.0
    )
    assert coord.stats()["lease_reclaims"] >= 1
    # ...and the next renewer picks the whole ceiling back up.
    client.renew_once()
    assert client.fraction("t") == pytest.approx(1.0)


def test_client_partition_bounded_staleness_then_heal():
    ttl = 0.4
    coord = LeaseCoordinator({"t": 40.0}, ttl_s=ttl)
    client = LeaseClient(coord, "h", renew_interval_s=0.05).start()
    adm = LeasedAdmission(
        client, [TenantPolicy("t", rate=40.0, burst=8, max_inflight=8)]
    )
    try:
        assert _wait_until(lambda: client.fraction("t") > 0.9)
        with adm.admit("t"):
            pass

        client.partition()
        # Bounded staleness: the cached lease stays usable only until
        # USE_FRACTION·TTL, then the host sheds with the typed reason.
        assert _wait_until(
            lambda: client.fraction("t") == 0.0, timeout_s=3.0
        )
        with pytest.raises(ShedError) as ei:
            adm.admit("t")
        assert ei.value.reason == "lease"
        assert ei.value.retriable

        client.partition(False)
        assert _wait_until(lambda: client.fraction("t") > 0.0)
        with adm.admit("t"):
            pass
    finally:
        client.close()


# ---------------------------------------------------------------------------
# LeasedAdmission: rate/quota scaled by the live fraction
# ---------------------------------------------------------------------------


def _single_host_rig(policies, *, ttl_s=5.0):
    coord = LeaseCoordinator(
        {p.name: p.rate for p in policies}, ttl_s=ttl_s
    )
    client = LeaseClient(coord, "h0", renew_interval_s=60.0)
    client.renew_once()  # fraction 1.0, usable for USE_FRACTION·ttl
    return coord, client, LeasedAdmission(client, policies)


def test_leased_admission_rate_bucket_sheds_typed_past_depth():
    _, _, adm = _single_host_rig([TenantPolicy("t", rate=10.0, burst=3)])
    for _ in range(3):  # bucket starts full at depth × fraction (= 3)
        with adm.admit("t"):
            pass
    with pytest.raises(ShedError) as ei:
        adm.admit("t")
    assert ei.value.reason == "rate"
    assert len(adm.admit_times()) == 3  # evidence trail: admits only


def test_leased_admission_quota_scales_with_fraction():
    """Two hosts at 1/2 each: a max_inflight=5 tenant gets floor(5·0.5)=2
    slots per host — the global quota never multiplies across the fleet."""
    pol = TenantPolicy("t", max_inflight=5)
    coord = LeaseCoordinator({"t": 0.0}, ttl_s=5.0)
    c1 = LeaseClient(coord, "h1", renew_interval_s=60.0)
    c2 = LeaseClient(coord, "h2", renew_interval_s=60.0)
    for c in (c1, c2, c1, c2):  # two rounds: converge to 1/2 each
        c.renew_once()
    assert c1.fraction("t") == pytest.approx(0.5)
    adm = LeasedAdmission(c1, [pol])
    with adm.admit("t"), adm.admit("t"):
        with pytest.raises(ShedError) as ei:
            adm.admit("t")
        assert ei.value.reason == "quota"
    with adm.admit("t"):  # released slots come back
        pass
    # Unlimited-rate tenants stay OUT of the rate-evidence trail.
    assert adm.admit_times() == []


def test_leased_admission_no_lease_sheds_lease_reason():
    coord = LeaseCoordinator({"t": 20.0}, ttl_s=5.0)
    client = LeaseClient(coord, "h", renew_interval_s=60.0)  # never renewed
    adm = LeasedAdmission(client, [TenantPolicy("t", rate=20.0)])
    with pytest.raises(ShedError) as ei:
        adm.admit("t")
    assert ei.value.reason == "lease"


# ---------------------------------------------------------------------------
# kill -9: lease reclaim + redistribution, over-admission pinned impossible
# ---------------------------------------------------------------------------


def test_kill9_slices_redistribute_within_ttl_and_never_overcommit():
    """THE lease-expiry correctness drill (a real kill -9): the dead
    replica's slices expire at the TTL and the survivors' summed ceiling
    returns to full — while a background sampler proves the summed usable
    fraction never exceeded 1.0 at any instant through the hand-off."""
    ttl = 0.5
    tenants = [TenantPolicy("gold", priority=2, rate=90.0, max_inflight=30)]
    fleet = build_fleet(
        replicas=3, tenants=tenants, ttl_s=ttl, engine_latency_s=0.0
    )
    try:
        hosts = fleet.hosts
        assert _wait_until(
            lambda: all(h.client.fraction("gold") > 0.30 for h in hosts)
        ), [h.client.fraction("gold") for h in hosts]

        sums = []
        stop = threading.Event()

        def sample():
            while not stop.is_set():
                t0 = time.monotonic()
                total = sum(h.client.fraction("gold") for h in hosts)
                # Only near-instant scans count: a scan preempted across
                # the USE_FRACTION→TTL gap would mix two instants.
                if time.monotonic() - t0 < 0.02:
                    sums.append(total)
                time.sleep(0.002)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()

        victim, survivors = hosts[-1], hosts[:-1]
        t_kill = time.monotonic()
        victim.kill()  # kill -9: renewals stop with the process
        assert _wait_until(
            lambda: sum(h.client.fraction("gold") for h in survivors)
            >= 0.99,
            timeout_s=6.0,
        )
        recovered_in = time.monotonic() - t_kill
        stop.set()
        sampler.join(timeout=2.0)

        # Reclaim ≤ TTL after the last renew, + one renew round to
        # converge — 2.5×TTL bounds it with scheduler slack.
        assert recovered_in < 2.5 * ttl, recovered_in
        assert victim.client.fraction("gold") == 0.0
        assert sums and max(sums) <= 1.0 + 1e-6, max(sums, default=0.0)
        assert fleet.coordinator.stats()["lease_reclaims"] >= 1
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# FleetRouter: spread, drain-by-cause, typed reroute, session affinity
# ---------------------------------------------------------------------------


def test_router_smooth_wrr_exact_weighted_spread():
    counts = {"a": 0, "b": 0}
    r = FleetRouter([
        ReplicaHandle("a", lambda p: counts.__setitem__(
            "a", counts["a"] + 1), weight=1.0),
        ReplicaHandle("b", lambda p: counts.__setitem__(
            "b", counts["b"] + 1), weight=3.0),
    ])
    for i in range(40):
        r.route(i)
    assert counts == {"a": 10, "b": 30}  # exact, deterministic, no RNG


def test_router_drain_excludes_until_undrain():
    served = []
    r = FleetRouter([
        ReplicaHandle("a", lambda p: served.append("a")),
        ReplicaHandle("b", lambda p: served.append("b")),
    ])
    r.drain("b")
    for i in range(6):
        r.route(i)
    assert served == ["a"] * 6
    r.undrain("b")
    served.clear()
    for i in range(6):
        r.route(i)
    assert "b" in served


def test_router_drains_swap_in_flight_but_keeps_routing_to_shedding():
    """Drain-by-CAUSE: pulling an overloaded replica out of rotation
    would concentrate load on its siblings — "shedding" stays routable;
    "swap_in_flight" is the wave's drain and gets no new traffic."""
    served = []
    r = FleetRouter([
        ReplicaHandle(
            "shed", lambda p: served.append("shed"),
            health_fn=lambda: {"status": "degraded",
                               "reasons": ["shedding"]},
        ),
        ReplicaHandle(
            "swap", lambda p: served.append("swap"),
            health_fn=lambda: {"status": "degraded",
                               "reasons": ["swap_in_flight"]},
        ),
    ])
    for i in range(5):
        r.route(i)
    assert served == ["shed"] * 5
    with pytest.raises(NoReplicaError):  # both mid-swap → typed, no hang
        FleetRouter([
            ReplicaHandle(
                "s1", lambda p: p,
                health_fn=lambda: {"status": "degraded",
                                   "reasons": ["swap_in_flight"]},
            ),
        ]).route(0)


def test_router_host_lost_reroutes_to_sibling_then_typed_exhaustion():
    a_dead = []

    def z_call(p):
        raise HostLostError("replica z died mid-call")

    def a_call(p):
        if a_dead:
            raise HostLostError("replica a died mid-call")
        return ("ok", p)

    # Names chosen so the WRR tie-break picks the dying replica first.
    r = FleetRouter([
        ReplicaHandle("a", a_call),
        ReplicaHandle("z", z_call),
    ])
    result, name, _version = r.route(7)
    assert result == ("ok", 7) and name == "a"  # rerouted, not dropped
    snap = r.stats()
    assert snap["reroutes"] == 1
    assert snap["healthy_replicas"] == 1  # z is marked lost
    # z stays out of rotation without further probing.
    assert r.route(8)[1] == "a"

    a_dead.append(True)
    with pytest.raises(NoReplicaError):  # last sibling died → typed
        r.route(9)
    assert r.stats()["reroutes"] == 2
    r.revive("a")
    a_dead.clear()
    assert r.route(10)[1] == "a"  # revive returns it to rotation


def test_router_probe_exception_means_lost():
    def bad_probe():
        raise ConnectionError("health endpoint unreachable")

    served = []
    r = FleetRouter([
        ReplicaHandle("a", lambda p: served.append("a"),
                      health_fn=bad_probe),
        ReplicaHandle("b", lambda p: served.append("b")),
    ])
    for i in range(4):
        r.route(i)
    assert served == ["b"] * 4


def test_router_session_affinity_pins_and_repins_monotone():
    ver = {"a": 1, "b": 1}
    r = FleetRouter([
        ReplicaHandle("a", lambda p: p, version_fn=lambda: ver["a"]),
        ReplicaHandle("b", lambda p: p, version_fn=lambda: ver["b"]),
    ])
    _, _, v = r.route(0, session="s")
    assert v == 1
    ver["b"] = 2  # b publishes v2 mid-wave
    _, name, v = r.route(1, session="s")
    assert v == 1 and name == "a"  # pinned: never mixes versions
    assert r.stats()["affinity_hits"] >= 1
    _, _, v_new = r.route(2, session="fresh")
    assert v_new == 2  # new sessions pin the newest routable version

    ver["a"] = 2  # pin target retired; session is idle → re-pin upward
    _, _, v = r.route(3, session="s")
    assert v == 2
    ver["a"] = ver["b"] = 1  # versions can never roll backward mid-session
    with pytest.raises(NoReplicaError):
        r.route(4, session="s")


def test_router_refuses_repin_while_session_has_inflight():
    """The two-versions-one-session races are refused, not served: a
    session whose pinned version retires while a request is still in
    flight gets a typed error until the request drains."""
    ver = {"a": 1, "b": 1}
    entered, release = threading.Event(), threading.Event()

    def slow_call(p):
        entered.set()
        assert release.wait(5.0)
        return p

    r = FleetRouter([
        ReplicaHandle("a", slow_call, version_fn=lambda: ver["a"],
                      weight=2.0),  # weight makes "a" the first pick
        ReplicaHandle("b", lambda p: p, version_fn=lambda: ver["b"]),
    ])
    errs = []

    def client():
        try:
            r.route(0, session="s")
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    t = threading.Thread(target=client, daemon=True)
    t.start()
    assert entered.wait(5.0)
    ver["a"] = ver["b"] = 2  # swap lands while the request is in flight
    with pytest.raises(NoReplicaError):
        r.route(1, session="s")
    release.set()
    t.join(timeout=5.0)
    assert not errs
    _, _, v = r.route(2, session="s")  # idle now → clean upward re-pin
    assert v == 2


def test_router_wait_idle_timeout_is_typed():
    entered, release = threading.Event(), threading.Event()

    def slow_call(p):
        entered.set()
        assert release.wait(5.0)
        return p

    r = FleetRouter([ReplicaHandle("a", slow_call)])
    t = threading.Thread(target=lambda: r.route(0), daemon=True)
    t.start()
    assert entered.wait(5.0)
    assert r.inflight("a") == 1
    with pytest.raises(TimeoutError):
        r.wait_idle("a", timeout_s=0.05)
    release.set()
    t.join(timeout=5.0)
    r.wait_idle("a", timeout_s=5.0)  # drained → returns


# ---------------------------------------------------------------------------
# WaveController: ordered fan-out, lost replicas skipped
# ---------------------------------------------------------------------------


def test_wave_swaps_in_declared_order_and_skips_lost():
    log = []
    lost = {"b"}

    def handle(name):
        return ReplicaHandle(
            name, lambda p: p,
            health_fn=lambda: (
                {"status": "lost", "reasons": ["host_lost"]}
                if name in lost else {"status": "ok", "reasons": []}
            ),
            swap_fn=lambda: log.append(name),
        )

    r = FleetRouter([handle("a"), handle("b"), handle("c")])
    waves = WaveController(r, drain_timeout_s=1.0)
    result = waves.run_wave()
    assert result["wave_id"] == 1
    assert result["swapped"] == ["a", "c"] == log  # wave order, b skipped
    assert result["skipped"] == ["b"]
    assert result["duration_s"] >= 0.0

    lost.clear()  # b restarted: the next wave picks it up
    log.clear()
    result = waves.run_wave()
    assert result["swapped"] == ["a", "b", "c"] == log
    assert waves.stats() == {"wave_id": 2}
    # A wave leaves nothing drained behind.
    for name in ("a", "b", "c"):
        assert r.route(0)[1] in ("a", "b", "c")


# ---------------------------------------------------------------------------
# Engine-backed acceptance: rolling swap wave, compile_count flat
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_engine():
    import jax
    from flax import linen as nn

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.serve import InferenceEngine
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig

    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    imgs = np.zeros((1, 16, 16, 3), np.float32)
    toks = np.zeros((1, cfg.text.context_length), np.int32)
    params = nn.meta.unbox(
        model.init(jax.random.key(0), imgs, toks)["params"]
    )
    eng = InferenceEngine.from_model(model, params, batch_buckets=(1,))
    eng.warmup()
    return eng


def test_rolling_swap_wave_engine_backed_zero_errors_compile_flat(
    fleet_engine,
):
    """THE fleet acceptance drill, engine-backed: 3 replicas serving a
    real (tiny) engine under concurrent multi-session load while three
    swap waves roll through. Zero client errors, per-session versions
    monotone (never two versions for one session), compile_count exactly
    where warmup left it — the zero-downtime contract at fleet scope."""
    eng = fleet_engine
    warmed = eng.compile_count
    img = np.zeros((1, 16, 16, 3), np.float32)

    def compute(body):
        return eng.encode_image(img)

    def swap_impl():
        eng.swap_params(eng.params)  # hot publish: same tree, no compile

    fleet = build_fleet(
        replicas=3,
        tenants=[TenantPolicy("gold", priority=2, max_inflight=64)],
        ttl_s=5.0,
        renew_interval_s=0.05,
        process_backed=False,
        computes=[compute] * 3,
        swap_impls=[swap_impl] * 3,
        drain_timeout_s=5.0,
    )
    try:
        assert _wait_until(
            lambda: all(
                h.client.fraction("gold") > 0.25 for h in fleet.hosts
            )
        )
        errors, seen = [], {}
        stop = threading.Event()

        def client(sid):
            session = f"sess-{sid}"
            rows = seen.setdefault(session, [])
            while not stop.is_set():
                try:
                    _res, _name, version = fleet.router.route(
                        ("gold", 1, sid), session=session
                    )
                except Exception as e:
                    errors.append(repr(e))
                    return
                rows.append(version)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        wave_results = []
        for _ in range(3):
            time.sleep(0.15)
            wave_results.append(fleet.waves.run_wave())
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)

        assert errors == []
        assert all(rows for rows in seen.values())
        for session, rows in seen.items():
            assert rows == sorted(rows), (session, rows)  # monotone
            assert 1 <= rows[0] and rows[-1] <= 4, (session, rows)
        # Someone rode all three waves to the final version.
        assert any(rows[-1] == 4 for rows in seen.values()), seen
        for w in wave_results:
            assert w["swapped"] == ["replica-0", "replica-1", "replica-2"]
            assert w["skipped"] == []
        assert fleet.waves.stats() == {"wave_id": 3}
        assert eng.compile_count == warmed  # not one fresh program
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Fleet scenarios: schema-valid records, the three drills
# ---------------------------------------------------------------------------


def test_build_fleet_and_scenario_grammar_are_validated():
    with pytest.raises(ValueError):
        build_fleet(replicas=1, tenants=[TenantPolicy("t")])
    with pytest.raises(ValueError):
        run_fleet_scenario("fleet-wat")


def test_fleet_hostloss_scenario_record():
    record = run_fleet_scenario(
        "fleet-hostloss", duration_s=1.5, offered_load=120.0,
        lease_ttl_s=0.3, seed=3,
    )
    assert record["metric"] == "fleet_siege"
    assert record["scenario"] == "fleet-hostloss"
    assert record["fleet_replicas"] == 3
    assert record["silent_drops"] == 0
    assert record["restarts"] == 1
    assert record["recovery_time_s"] > 0
    assert record["lease_reclaims"] >= 1  # the dead host's slices aged out
    assert record["over_ceiling_samples"] == 0
    assert record["peak_admitted_rate"] >= 0.0
    assert validate_record(record) == []


def test_fleet_splitbrain_scenario_under_admits_never_over():
    record = run_fleet_scenario(
        "fleet-splitbrain", duration_s=2.0, offered_load=120.0,
        lease_ttl_s=0.3, seed=4,
    )
    assert record["silent_drops"] == 0
    assert record["over_ceiling_samples"] == 0  # the split-brain proof
    assert record["shed_rate"] > 0  # under-admission is visible, not free
    assert record["lease_reclaims"] >= 1
    assert record["restarts"] == 0  # partition, not a death
    assert validate_record(record) == []


def test_fleet_rolling_swap_scenario_waves_under_burst():
    record = run_fleet_scenario(
        "fleet-rolling-swap", duration_s=1.5, offered_load=100.0,
        lease_ttl_s=0.5, seed=5,
    )
    assert record["silent_drops"] == 0
    assert record["wave_id"] >= 2  # a wave every ~200ms over the soak
    assert record["over_ceiling_samples"] == 0
    assert record["replica_count"] == 3
    assert validate_record(record) == []


@pytest.mark.slow
def test_fleet_scenarios_extended_soak():
    for scenario, seed in (
        ("fleet-hostloss", 13), ("fleet-splitbrain", 17),
        ("fleet-rolling-swap", 19),
    ):
        record = run_fleet_scenario(
            scenario, duration_s=5.0, offered_load=160.0,
            lease_ttl_s=0.5, seed=seed,
        )
        assert record["silent_drops"] == 0, scenario
        assert record["over_ceiling_samples"] == 0, scenario
        assert validate_record(record) == [], scenario


# ---------------------------------------------------------------------------
# serve-bench --fleet-scenario CLI: grammar + the in-process record path
# ---------------------------------------------------------------------------


def test_cli_fleet_grammar_refusals_exit_2():
    from distributed_sigmoid_loss_tpu.cli import main as cli_main

    assert cli_main(
        ["serve-bench", "--fleet-scenario", "fleet-hostloss",
         "--scenario", "burst"]
    ) == 2  # one drill per run
    assert cli_main(["serve-bench", "--fleet-replicas", "3"]) == 2
    assert cli_main(["serve-bench", "--lease-ttl-s", "0.5"]) == 2
    assert cli_main(
        ["serve-bench", "--fleet-scenario", "fleet-hostloss",
         "--fleet-replicas", "1"]
    ) == 2  # no sibling to reroute to


def test_cli_fleet_hostloss_emits_schema_valid_record(capsys):
    from distributed_sigmoid_loss_tpu.cli import main as cli_main

    rc = cli_main(
        ["serve-bench", "--fleet-scenario", "fleet-hostloss",
         "--fleet-replicas", "3", "--lease-ttl-s", "0.3",
         "--duration-s", "1.2", "--offered-load", "100", "--seed", "3"]
    )
    out = capsys.readouterr().out
    assert rc == 0, out
    record = json.loads(out.strip().splitlines()[-1])
    assert record["metric"] == "fleet_siege"
    assert record["silent_drops"] == 0
    assert record["over_ceiling_samples"] == 0
    assert validate_record(record) == []
