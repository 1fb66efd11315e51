"""Scenario foundry + chaos soak harness (PR 8).

Covers: the composable arrival envelopes, seeded ``SimTandem``
determinism and the concurrent-drain queue recursion (flow must not be
throttled to ~capacity items/period), Pareto service carry,
``FaultPlan.chaos`` edge cases (empty plan audit, overlapping events,
zero-length skew windows, targets validation, seed-prefix schedule
stability), the sim-time ``StormDriver``, cell/matrix runs reproducing
bit-for-bit under one seed, trace record -> npz roundtrip -> replay
reproducing the decision sequence exactly (the determinism regression
gate), ``ControlLog.drain_jsonl`` + the monotonic/wall timestamp pair,
per-class deadline-drop accounting under sustained load, and the
engine's monitor watchdog (new wiring this PR).
"""

import json
import time

import numpy as np
import pytest

from repro.control import ControlLog, ControlRecord
from repro.ft import FaultEvent, FaultPlan, InjectedFault
from repro.workloads import (Boxcar, Constant, Diurnal, FlashCrowd,
                             ParetoService, Ramp, SimActuator, SimTandem,
                             Square, Step, StormDriver, Trace, make_policies,
                             replay, run_cell, run_matrix)

# -- arrival envelopes ------------------------------------------------------


def test_envelope_shapes():
    assert Step(60, 15, at=100).rate(99.9) == 60
    assert Step(60, 15, at=100).rate(100.0) == 15
    r = Ramp(0, 10, t0=0, t1=10)
    assert r.rate(-1) == 0 and r.rate(5) == pytest.approx(5)
    assert r.rate(11) == 10
    sq = Square(160, 40, period=200)
    assert sq.rate(0) == 160 and sq.rate(100) == 40
    # half-period shift makes the anti-correlated partner
    assert sq.shift(100).rate(0) == 40
    d = Diurnal(base=100, amplitude=60, period=400)
    assert d.rate(100) == pytest.approx(160)
    assert Diurnal(base=10, amplitude=60, period=400).rate(300) == 0.0
    b = Boxcar(50, t0=10, t1=20)
    assert b.rate(9.9) == 0 and b.rate(10) == 50 and b.rate(20) == 0
    fc = FlashCrowd(peak=300, at=100, rise=50, fall=20)
    assert fc.rate(49) == 0.0
    assert fc.rate(75) == pytest.approx(150)
    assert fc.rate(100) == pytest.approx(300)
    assert fc.rate(120) == pytest.approx(300 * np.exp(-1))


def test_envelope_composition():
    lam = Constant(100) + Boxcar(50, 10, 20)
    assert lam.rate(5) == 100 and lam.rate(15) == 150
    assert (Constant(10) * 2.5).rate(0) == 25
    assert (2.5 * Constant(10)).rate(0) == 25
    assert (Constant(10) + 5).rate(0) == 15
    assert Ramp(0, 100, 0, 10).clip(20, 80).rate(0) == 20
    assert Ramp(0, 100, 0, 10).clip(20, 80).rate(10) == 80
    with pytest.raises(ValueError):
        Ramp(0, 1, t0=5, t1=5)
    with pytest.raises(ValueError):
        Square(1, 0, period=0)
    with pytest.raises(ValueError):
        FlashCrowd(peak=1, at=0, rise=0, fall=1)


# -- simulated tandem -------------------------------------------------------


def test_sim_tandem_seeded_determinism():
    mk = lambda s: SimTandem(s, Constant(100), Constant(60), 2, 64)  # noqa
    a, b, c = mk(7), mk(7), mk(8)
    ra = [a.step(float(t)) for t in range(200)]
    rb = [b.step(float(t)) for t in range(200)]
    rc = [c.step(float(t)) for t in range(200)]
    assert ra == rb
    assert ra != rc


def test_sim_tandem_flow_not_capacity_throttled():
    # cap-16 queue, ample service: the concurrent-drain recursion must
    # flow ~lam items/period, not ~capacity items/period (the
    # accept-then-serve ordering bug this PR's sim replaced)
    sim = SimTandem(0, Constant(100), Constant(60), 2, 16)
    for t in range(100):
        sim.step(float(t))
    assert sim.served_total >= 0.9 * sim.offered_total
    assert sim.served_total > 3 * 16 * 100 / 10      # >> cap/period flow
    # conservation: offered = served + queued + shed + blocked-at-tail
    # (items refused by a full queue are lost to the sim, not queued)
    lost = sim.offered_total - (sim.served_total + sim.backlog
                                + sim.shed_total)
    assert 0 <= lost <= 0.01 * sim.offered_total


def test_sim_tandem_fault_knobs():
    sim = SimTandem(0, Constant(100), Constant(60), 3, 256)
    assert sim.kill_replica() and sim.replicas == 2 and sim.killed == 1
    sim.replicas = 1
    assert not sim.kill_replica()          # never kills the last replica
    sim.meas_scale = 0.5                   # skewed measurement:
    tt, _, ht, _ = sim.step(0.0)           # counters halved,
    assert tt == int(tt * 2) / 2.0
    assert sim.occupancy <= 1.0            # physics untouched


def test_pareto_service_carry_and_validation():
    with pytest.raises(ValueError):
        ParetoService(Constant(60), alpha=1.0)
    svc = ParetoService(Constant(0.02), alpha=1.05)   # huge mean cost
    rng = np.random.default_rng(0)
    draws = [svc.draw(rng, 0.0, 1) for _ in range(50)]
    assert any(d == 0 for d in draws)       # an item spans whole periods
    assert svc._rem >= 0.0
    # clone() must not share carry state
    svc._rem = 123.0
    assert svc.clone()._rem == 0.0


# -- FaultPlan.chaos edge cases ---------------------------------------------


def test_chaos_empty_plan_audit():
    plan = FaultPlan.chaos(seed=0, targets=[], n_crashes=0).arm()
    assert plan.pending() == 0
    assert plan.fired() == []
    assert plan.events() == ()
    assert plan.skew_factor() == 1.0
    assert plan.worker_fault_due("anything") is None
    assert not plan.monitor_death_due()


def test_chaos_crashes_without_targets_raise():
    with pytest.raises(ValueError):
        FaultPlan.chaos(seed=0, targets=[], n_crashes=1)
    with pytest.raises(ValueError):
        FaultPlan.chaos(seed=0, targets=(), n_crashes=0, n_stalls=2)
    # skew-only storms legitimately target nothing
    p = FaultPlan.chaos(seed=0, targets=[], n_crashes=0, n_skews=2,
                        skew_s=1.0, skew_factor=2.0)
    assert p.pending() == 2


def test_overlapping_events_both_fire():
    plan = FaultPlan([FaultEvent(0.0, "crash", "work"),
                      FaultEvent(0.0, "crash", "work"),
                      FaultEvent(0.0, "stall", "work",
                                 duration_s=0.0)]).arm()
    for _ in range(2):
        with pytest.raises(InjectedFault):
            plan.maybe_fault("work")
    plan.maybe_fault("work")               # the zero-length stall
    assert plan.pending() == 0
    assert sorted(e.kind for _, e in plan.fired()) == [
        "crash", "crash", "stall"]


def test_zero_length_skew_window_never_active():
    plan = FaultPlan([FaultEvent(0.5, "clock_skew", duration_s=0.0,
                                 factor=3.0)])
    t0 = time.monotonic()
    plan.arm(t0 - 0.5)                     # exactly at the window start
    assert plan.skew_factor() == 1.0
    assert plan.skew_factor(now=t0 + 123.0) == 1.0


def test_chaos_schedule_seed_prefix_stable():
    base = FaultPlan.chaos(seed=11, targets=["a", "b"], n_crashes=2,
                           n_stalls=1)
    more = FaultPlan.chaos(seed=11, targets=["a", "b"], n_crashes=2,
                           n_stalls=1, n_skews=3, skew_s=0.5,
                           skew_factor=2.0, monitor_death_at=1.0)
    key = lambda e: (e.at_s, e.kind, e.target, e.duration_s)  # noqa
    # events() is a chronological view, so compare as schedules: every
    # draw of the shorter plan appears unchanged in the extended one
    small = sorted(key(e) for e in base.events())
    big = sorted(key(e) for e in more.events())
    assert all(k in big for k in small)


# -- sim-time storm driver --------------------------------------------------


def test_storm_driver_sim_time_semantics():
    plan = FaultPlan([
        FaultEvent(2.0, "crash", "a"),
        FaultEvent(4.0, "stall", "a", duration_s=3.0),
        FaultEvent(6.0, "monitor_death", duration_s=2.0),
        FaultEvent(8.0, "clock_skew", duration_s=2.0, factor=2.0)])
    drv = StormDriver(plan)
    sims = {"a": SimTandem(0, Constant(10), Constant(10), 3, 64)}
    assert drv.apply(0.0, sims)
    assert sims["a"].replicas == 3
    drv.apply(2.0, sims)
    assert sims["a"].replicas == 2         # crash fired
    drv.apply(4.0, sims)
    assert sims["a"].stalled == 1          # stall window open
    assert not drv.apply(6.0, sims)        # monitor outage: no sampling
    assert not drv.apply(7.0, sims)        # ...still dark
    assert sims["a"].stalled == 0          # stall expired meanwhile
    assert drv.apply(8.5, sims)            # outage over; skew active
    assert sims["a"].meas_scale == pytest.approx(0.5)
    drv.apply(10.0, sims)
    assert sims["a"].meas_scale == 1.0     # skew window closed
    assert drv.fired_kinds == ["crash", "stall", "monitor_death"]
    # the driver audits locally: the plan's wall-clock API is untouched
    assert plan.fired() == []


# -- cells, matrix, replay --------------------------------------------------


def test_run_cell_seeded_reproducibility():
    a = run_cell("step", "replica", "storm", seed=3, quick=True)
    b = run_cell("step", "replica", "storm", seed=3, quick=True)
    assert np.array_equal(a.served, b.served)
    assert a.row() == b.row()
    assert a.faults_fired                  # the storm actually fired


def test_replay_reproduces_decision_sequence(tmp_path):
    c = run_cell("step", "full", "storm", seed=5, quick=True,
                 record=True)
    assert c.trace is not None
    p = tmp_path / "cell.npz"
    c.trace.save(p)
    tr = Trace.load(p)
    assert tr.meta["scenario"] == "step"
    out = replay(tr, make_policies(
        "full", decide_every=tr.meta["decide_every"]))
    for f, want in tr.decisions.items():
        assert np.array_equal(out[f], want), f"replay diverged on {f}"
    # counterfactual: a different PolicySet replays against the same
    # recorded observations without error (and may decide differently)
    cf = replay(tr, make_policies(
        "replica", decide_every=tr.meta["decide_every"]))
    assert cf["target_replicas"].shape == tr.decisions[
        "target_replicas"].shape


@pytest.mark.slow
def test_matrix_quick_acceptance():
    m = run_matrix(seed=0, quick=True)
    assert m["n_cells"] >= 12
    ctl = [c for c in m["cells"] if c["policy"] != "static"]
    assert min(c["availability"] for c in ctl) >= 0.9
    storm = [c for c in ctl if c["fault"] != "none"]
    assert min(c["vs_static"] for c in storm) >= 1.2


@pytest.mark.soak
def test_matrix_full_soak():
    m = run_matrix(seed=0, quick=False)
    assert m["n_cells"] >= 12
    ctl = [c for c in m["cells"] if c["policy"] != "static"]
    assert min(c["availability"] for c in ctl) >= 0.9


# -- control log drain + timestamp pair -------------------------------------


def _rec(i):
    return ControlRecord(tick=i, t=time.monotonic(), queue=0,
                         policy="replicas", observed_lam=1.0,
                         observed_mu=2.0, action="scale", value=i,
                         outcome="applied")


def test_control_record_timestamp_pair():
    before = time.time()
    r = _rec(0)
    assert before <= r.t_wall <= time.time()
    assert r.t_wall == pytest.approx(time.time(), abs=60)
    assert r.t != r.t_wall                 # monotonic vs wall epoch


def test_drain_jsonl_incremental(tmp_path):
    log = ControlLog(capacity=4)
    path = tmp_path / "log.jsonl"
    for i in range(3):
        log.append(_rec(i))
    assert log.drain_jsonl(path) == 3
    assert log.drain_jsonl(path) == 0      # idempotent between appends
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x["tick"] for x in lines] == [0, 1, 2]
    assert all("t_wall" in x and "t" in x for x in lines)


def test_drain_jsonl_acknowledges_ring_drop(tmp_path):
    log = ControlLog(capacity=4)
    path = tmp_path / "log.jsonl"
    log.append(_rec(0))
    assert log.drain_jsonl(path) == 1
    for i in range(1, 8):                  # wraps: ticks 1..3 fall off
        log.append(_rec(i))
    assert log.drain_jsonl(path) == 4
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert {"dropped": 3} in lines
    assert [x["tick"] for x in lines if "tick" in x] == [0, 4, 5, 6, 7]


# -- engine: deadline accounting + monitor watchdog -------------------------


def _work_engine(scfg, work_s, **kw):
    from repro.serve import Engine

    class _Work(Engine):
        def _serve_batch(self, batch):
            time.sleep(work_s)
            for r in batch:
                r.out = np.zeros(1, np.int32)
                r.done.set()
                self.served += 1

    return _Work(None, None, scfg, **kw)


def test_per_class_deadline_drops_under_sustained_load():
    from repro.serve import BLOCKING, NONBLOCKING, Request, ServeConfig
    from repro.streams import CounterArena
    eng = _work_engine(
        ServeConfig(batch_size=1, queue_capacity=256, bulkheads=(1, 1)),
        work_s=0.02, arena=CounterArena(8))
    eng.start()
    try:
        for i in range(40):                # ~0.8s of work vs 50ms budget
            eng.submit(Request(rid=i, tokens=np.arange(4), max_new=1,
                               qos=BLOCKING, deadline_s=0.05),
                       timeout=0.01)
        for i in range(40, 50):            # undeadlined patient traffic
            eng.submit(Request(rid=i, tokens=np.arange(4), max_new=1,
                               qos=NONBLOCKING), timeout=0.01)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = eng.admission_state()["classes"]
            if st[BLOCKING]["deadline_dropped"] >= 5:
                break
            time.sleep(0.05)
        st = eng.admission_state()["classes"]
        b = st[BLOCKING]
        assert b["deadline_dropped"] >= 5
        assert st[NONBLOCKING]["deadline_dropped"] == 0
        # accounting identity per class: nothing vanishes
        assert b["served"] + b["deadline_dropped"] <= b["admitted"]
    finally:
        eng.stop()


def test_engine_monitor_watchdog_restarts_dead_thread():
    from repro.serve import ServeConfig
    from repro.streams import CounterArena
    plan = FaultPlan([FaultEvent(0.5, "monitor_death")])
    eng = _work_engine(
        ServeConfig(batch_size=1, queue_capacity=16, bulkheads=(1, 1)),
        work_s=0.0, arena=CounterArena(8), control=True, fault_plan=plan)
    # compile before the clock starts: the thread's own warm-up is then
    # a cache hit, and a loaded host cannot stretch it past the join
    eng.fleet.warmup()
    plan.arm()
    eng.start()
    try:
        dead = eng.monitor_thread
        dead.join(timeout=10)              # injected silent death
        assert not dead.is_alive()
        # the engine's running loop polls too: whichever poll comes
        # first restarts the thread, and the other finds it alive
        eng.control.check_monitor()
        assert eng.monitor_thread is not dead
        assert eng.monitor_thread.is_alive()
        assert eng.control.health()["monitor_restarts"] == 1
        assert [r.error for r in eng.control.log.records()
                if r.policy == "watchdog"] == ["E_MONITOR_DEAD"]
    finally:
        eng.stop()


# -- PR 9: injected actuation failures (sim-time twin of FaultyActuator) -----


def test_sim_actuator_injected_failure_consumed_once():
    """A pending failure makes exactly ONE matching verb raise before
    actuating anything; the next call goes through — the retry contract
    the control loop's rollback path is built against."""
    sim = SimTandem(0, Constant(100), Constant(60), 2, 64)
    act = SimActuator(sim, fail_verbs={"scale": 1})
    with pytest.raises(InjectedFault):
        act.scale(0, 5)
    assert sim.replicas == 2               # failed verb actuated nothing
    assert act.fail_verbs["scale"] == 0
    assert act.scale(0, 5) == "applied"    # consumed: next call applies
    assert sim.replicas == 5
    assert ("scale-injected-fail", -1) in act.actions


def test_storm_driver_routes_actuation_events_to_shared_gate():
    """An "actuation" storm event lands in the shared fail_verbs dict
    (sim-time twin of FaultyActuator): every actuator gating on that
    dict sees it, and the first matching verb consumes it."""
    plan = FaultPlan([FaultEvent(1.0, "actuation", "scale"),
                      FaultEvent(1.0, "actuation", "resize")])
    fail: dict = {}
    drv = StormDriver(plan, fail)
    sims = {"a": SimTandem(0, Constant(10), Constant(10), 2, 64)}
    act = SimActuator(sims["a"], fail_verbs=fail)
    assert drv.apply(0.0, sims)
    assert fail == {}
    drv.apply(1.0, sims)
    assert fail == {"scale": 1, "resize": 1}
    with pytest.raises(InjectedFault):
        act.scale(0, 3)
    with pytest.raises(InjectedFault):
        act.resize(0, 32)
    assert act.scale(0, 3) == "applied"
    assert act.resize(0, 128) == "applied"
    assert drv.fired_kinds == ["actuation", "actuation"]


def test_chaos_act_fail_draws_append_only_and_verb_targeted():
    """n_act_fails extends a chaos schedule without disturbing the
    earlier draws (seed-prefix stability), and each event targets an
    actuator verb, not a stage."""
    base = FaultPlan.chaos(seed=5, targets=["a"], n_crashes=2, n_stalls=1)
    more = FaultPlan.chaos(seed=5, targets=["a"], n_crashes=2, n_stalls=1,
                           n_act_fails=3)
    key = lambda e: (e.at_s, e.kind, e.target, e.duration_s)  # noqa
    small = sorted(key(e) for e in base.events())
    big = sorted(key(e) for e in more.events())
    assert all(k in big for k in small)
    acts = [e for e in more.events() if e.kind == "actuation"]
    assert len(acts) == 3
    assert all(e.target in ("scale", "resize", "admit") for e in acts)
