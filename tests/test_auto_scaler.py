"""Brain decision rules, execution arm, journaling, and the
DLROVER_TPU_BRAIN=0 seed pin.

The rule table drives ``ObservatoryBrainOptimizer.decide`` directly
with synthetic :class:`ObservatorySignals` (grow/shrink/drain
thresholds, sustain, cooldown suppression, hysteresis, min/max world
clamps, no-op on insufficient samples).  The executor tests run
against a REAL ``ElasticTrainingRendezvousManager`` so fencing and
world transitions are the product's, not a mock's.  The failover
tests replay captured journal records into a fresh Brain and assert
a mid-decision action resumes (directive re-armed) or abandons, and
that a just-issued shrink suppresses an immediate re-grow.
"""

import threading
import time

import pytest

from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.master.auto_scaler import (
    AllreduceAutoScaler,
    BrainAutoScaler,
)
from dlrover_tpu.master.brain import BrainExecutor, NodeDirectives
from dlrover_tpu.master.rendezvous import (
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.resource_optimizer import (
    ACTION_DRAIN_REPLACE,
    ACTION_GROW,
    ACTION_SHRINK,
    OUTCOME_DONE,
    OUTCOME_FENCED_FALLBACK,
    BrainDecision,
    ObservatoryBrainOptimizer,
    ObservatorySignals,
)

T0 = 1_000_000.0


def make_optimizer(**kw):
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("sustain_cycles", 2)
    return ObservatoryBrainOptimizer(**kw)


def signals(**kw):
    kw.setdefault("world", [0, 1, 2])
    kw.setdefault("min_nodes", 1)
    kw.setdefault("max_nodes", 4)
    kw.setdefault("now", T0)
    kw.setdefault("median_step_time_s", 0.2)
    return ObservatorySignals(**kw)


def drive(opt, sig_fn, cycles, t0=T0, dt=1.0):
    """Feed ``cycles`` snapshots; return the first decision."""
    for i in range(cycles):
        decision = opt.decide(sig_fn(now=t0 + i * dt))
        if decision is not None:
            return decision
    return None


class TestDecisionRules:
    def test_noop_on_empty_signals(self):
        opt = make_optimizer()
        assert opt.decide(ObservatorySignals(now=T0)) is None

    def test_noop_on_healthy_world(self):
        opt = make_optimizer()
        assert drive(opt, signals, 5) is None

    def test_straggler_needs_sustain(self):
        opt = make_optimizer(sustain_cycles=3)
        sig = lambda now: signals(  # noqa: E731
            stragglers=[(2, 3.5)], now=now
        )
        assert opt.decide(sig(now=T0)) is None
        assert opt.decide(sig(now=T0 + 1)) is None
        decision = opt.decide(sig(now=T0 + 2))
        assert decision is not None
        assert decision.action == ACTION_DRAIN_REPLACE
        assert decision.node == 2
        assert decision.from_world == 3
        assert decision.to_world == 2  # no launch capacity
        assert "straggler:3.5" in decision.reason

    def test_straggler_streak_resets_on_recovery(self):
        opt = make_optimizer(sustain_cycles=2)
        assert opt.decide(signals(stragglers=[(2, 3.0)])) is None
        # one healthy cycle clears the streak
        assert opt.decide(signals(now=T0 + 1)) is None
        assert (
            opt.decide(signals(stragglers=[(2, 3.0)], now=T0 + 2))
            is None
        )

    def test_drain_with_launch_capacity_keeps_world(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            stragglers=[(1, 4.0)], can_launch=True, now=now
        )
        decision = drive(opt, sig, 3)
        assert decision.action == ACTION_DRAIN_REPLACE
        assert decision.to_world == 3  # replaced, not shrunk

    def test_drain_clamped_at_min_nodes(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            world=[0, 1], min_nodes=2, stragglers=[(1, 4.0)], now=now
        )
        assert drive(opt, sig, 5) is None

    def test_hang_verdict_drains(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            hangs=[(1, 120.0)], median_step_time_s=0.0, now=now
        )
        decision = drive(opt, sig, 3)
        assert decision.action == ACTION_DRAIN_REPLACE
        assert decision.node == 1
        assert decision.reason.startswith("hang:")

    def test_fenced_node_not_re_planned(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            stragglers=[(2, 3.0)], fenced=[2], now=now
        )
        assert drive(opt, sig, 5) is None

    def test_chronic_stall_shrinks_worst_node(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            stall_shares={
                0: {"host_fetch": 0.5},
                1: {"host_fetch": 0.7},
                2: {"h2d": 0.1},
            },
            now=now,
        )
        decision = drive(opt, sig, 3)
        assert decision.action == ACTION_SHRINK
        assert decision.node == 1  # worst share
        assert decision.to_world == 2
        assert "data_stall:0.70" in decision.reason

    def test_one_stalled_node_is_not_chronic(self):
        """Half-the-world gate: a single unlucky node out of three
        must not shrink the job."""
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            stall_shares={1: {"host_fetch": 0.9}}, now=now
        )
        assert drive(opt, sig, 5) is None

    def test_shrink_clamped_at_min_nodes(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            world=[0], min_nodes=1,
            stall_shares={0: {"host_fetch": 0.9}}, now=now,
        )
        assert drive(opt, sig, 5) is None

    def test_grow_needs_capacity_and_launcher(self):
        opt = make_optimizer()
        # no scaler -> never grow
        assert drive(opt, signals, 5) is None
        # scaler but already at max
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            max_nodes=3, can_launch=True, now=now
        )
        assert drive(opt, sig, 5) is None

    def test_grow_on_linear_scaling(self):
        opt = make_optimizer()
        sig = lambda now: signals(can_launch=True, now=now)  # noqa: E731
        decision = drive(opt, sig, 4)
        assert decision is not None
        assert decision.action == ACTION_GROW
        assert decision.from_world == 3
        assert decision.to_world == 4
        assert decision.node == -1

    def test_grow_suppressed_on_sublinear_scaling(self):
        """Step time degraded >tolerance when the world grew: the
        knee is behind us, stop growing."""
        opt = make_optimizer()
        # warm the 2-node history WITHOUT launch capacity so the
        # warm-up itself cannot emit a grow decision
        for i in range(3):
            opt.decide(
                signals(
                    world=[0, 1], median_step_time_s=0.2,
                    can_launch=False, max_nodes=4, now=T0 + i,
                )
            )
        # world grew 2 -> 3 and step time jumped 40%
        sig = lambda now: signals(  # noqa: E731
            median_step_time_s=0.28, can_launch=True, now=now
        )
        assert drive(opt, sig, 5, t0=T0 + 10) is None

    def test_grow_needs_settled_cycles(self):
        """No samples at the current world size -> insufficient
        evidence -> no-op."""
        opt = make_optimizer(sustain_cycles=3)
        sig = lambda now: signals(can_launch=True, now=now)  # noqa: E731
        assert opt.decide(sig(now=T0)) is None
        assert opt.decide(sig(now=T0 + 1)) is None

    def test_grow_without_step_samples_is_noop(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            can_launch=True, median_step_time_s=0.0, now=now
        )
        assert drive(opt, sig, 5) is None


class TestCooldownHysteresis:
    def _shrink(self, opt, t):
        sig = lambda now: signals(  # noqa: E731
            stall_shares={
                0: {"host_fetch": 0.8},
                1: {"host_fetch": 0.8},
                2: {"host_fetch": 0.8},
            },
            now=now,
        )
        decision = drive(opt, sig, 4, t0=t)
        assert decision is not None and decision.action == ACTION_SHRINK
        return decision

    def test_in_flight_blocks_further_decisions(self):
        opt = make_optimizer()
        self._shrink(opt, T0)
        assert opt.in_flight is not None
        sig = lambda now: signals(  # noqa: E731
            stragglers=[(0, 9.0)], now=now
        )
        assert drive(opt, sig, 5, t0=T0 + 100) is None

    def test_cooldown_suppresses_same_direction(self):
        opt = make_optimizer(cooldown_s=10.0)
        self._shrink(opt, T0)
        opt.complete(OUTCOME_DONE, now=T0 + 5)
        sig = lambda now: signals(  # noqa: E731
            world=[0, 1], stragglers=[(1, 4.0)], now=now
        )
        # 5s after completion: inside the 10s cooldown
        assert drive(opt, sig, 3, t0=T0 + 8, dt=0.1) is None
        # past it: allowed (same direction)
        assert drive(opt, sig, 3, t0=T0 + 16) is not None

    def test_hysteresis_doubles_opposite_direction(self):
        """The flip-flop guard: a shrink at t means grow waits 2x
        cooldown, not 1x."""
        opt = make_optimizer(cooldown_s=10.0)
        self._shrink(opt, T0)
        opt.complete(OUTCOME_DONE, now=T0 + 5)
        grow_sig = lambda now: signals(  # noqa: E731
            world=[0, 1], can_launch=True, now=now
        )
        # warm the grow evidence (decide() also updates history)
        assert drive(opt, grow_sig, 3, t0=T0 + 16) is None  # < 2x
        assert drive(opt, grow_sig, 2, t0=T0 + 26) is not None


class TestJournalRoundTrip:
    def test_export_restore_identity(self):
        opt = make_optimizer()
        sig = lambda now: signals(  # noqa: E731
            stragglers=[(2, 3.0)], now=now
        )
        decision = drive(opt, sig, 3)
        assert decision is not None
        state = opt.export_state()
        clone = make_optimizer()
        clone.restore_state(state)
        assert clone.export_state() == state
        assert clone.in_flight.decision_id == decision.decision_id
        assert clone.in_flight.node == 2

    def test_restored_cooldown_suppresses_regrow(self):
        """The satellite pin: a failover must not forget a just-
        issued shrink and immediately re-grow."""
        opt = make_optimizer(cooldown_s=10.0)
        sig = lambda now: signals(  # noqa: E731
            stall_shares={
                0: {"host_fetch": 0.8},
                1: {"host_fetch": 0.8},
                2: {"host_fetch": 0.8},
            },
            now=now,
        )
        assert drive(opt, sig, 4) is not None
        opt.complete(OUTCOME_DONE, now=T0 + 5)
        reborn = make_optimizer(cooldown_s=10.0)
        reborn.restore_state(opt.export_state())
        grow_sig = lambda now: signals(  # noqa: E731
            world=[0, 1], can_launch=True, now=now
        )
        # inside the 2x-cooldown hysteresis window: suppressed
        assert drive(reborn, grow_sig, 4, dt=0.5, t0=T0 + 7) is None
        # well past it: allowed
        assert drive(reborn, grow_sig, 3, t0=T0 + 40) is not None


def completed_world(ranks, max_nodes=4):
    """A real rendezvous manager with a completed round over
    ``ranks``."""
    manager = ElasticTrainingRendezvousManager()
    manager.update_rdzv_params(1, max_nodes, 0.0, 1)
    for r in ranks:
        manager.join_rendezvous(r, 1)
    _rnd, _g, world = manager.get_comm_world(ranks[0])
    assert set(world) == set(ranks)
    return manager


class FakeHealth:
    def __init__(self):
        self.straggler_list = []
        self.hang_list = []
        self.stalls = {}
        self.median = 0.2

    def stragglers(self):
        return list(self.straggler_list)

    def hang_suspects(self):
        return list(self.hang_list)

    def stall_shares(self):
        return dict(self.stalls)

    def median_step_time(self):
        return self.median


def make_brain(manager, health=None, interval=3600.0, **opt_kw):
    opt_kw.setdefault("cooldown_s", 10.0)
    opt_kw.setdefault("sustain_cycles", 2)
    executor = BrainExecutor(
        rdzv_manager=manager, directives=NodeDirectives()
    )
    return BrainAutoScaler(
        ObservatoryBrainOptimizer(**opt_kw),
        executor,
        health_engine=health or FakeHealth(),
        interval=interval,
    )


class TestBrainLoop:
    def test_drain_posts_directive_and_completes_on_fence(self):
        manager = completed_world([0, 1, 2])
        health = FakeHealth()
        health.straggler_list = [(2, 4.0)]
        brain = make_brain(manager, health)
        journal = []
        brain.set_journal(lambda op, args: journal.append((op, args)))
        for i in range(3):
            brain.run_cycle(now=T0 + i)
        assert brain.optimizer.in_flight is not None
        assert brain.directives.peek(2) is not None
        assert journal, "the decision must be journaled"
        # the agent acks by reporting node_preempted -> fence
        manager.fence_node(2, ttl_s=60.0)
        brain.run_cycle(now=T0 + 3)
        assert brain.optimizer.in_flight is None
        assert brain.optimizer.last_decision.action == (
            ACTION_DRAIN_REPLACE
        )

    def test_deadline_falls_back_to_master_side_fence(self):
        manager = completed_world([0, 1, 2])
        health = FakeHealth()
        health.straggler_list = [(2, 4.0)]
        brain = make_brain(manager, health, interval=1.0)
        for i in range(3):
            brain.run_cycle(now=T0 + i)
        decision = brain.optimizer.in_flight
        assert decision is not None
        # nobody ever polls the directive; the deadline fences
        brain.run_cycle(now=decision.made_at + 10_000.0)
        assert brain.optimizer.in_flight is None
        assert 2 in manager.fenced_ranks()
        assert brain.directives.peek(2) is None

    def test_failover_mid_decision_resumes_directive(self):
        """Kill the master after the decision journaled but before
        the agent saw the directive: the next incarnation re-arms it
        from the journal instead of dropping or re-deciding."""
        manager = completed_world([0, 1, 2])
        health = FakeHealth()
        health.straggler_list = [(2, 4.0)]
        brain_a = make_brain(manager, health)
        records = []
        brain_a.set_journal(lambda op, args: records.append((op, args)))
        for i in range(3):
            brain_a.run_cycle(now=T0 + i)
        in_flight = brain_a.optimizer.in_flight
        assert in_flight is not None
        # --- the master dies here; replay into a fresh brain ---
        brain_b = make_brain(manager, health)
        for op, args in records:
            assert op == "state"
            brain_b.restore_state(args)
        assert brain_b.directives.peek(2) is None  # memory died
        brain_b.run_cycle(now=T0 + 4)
        resumed = brain_b.directives.peek(2)
        assert resumed is not None
        assert resumed[2] == in_flight.decision_id  # SAME decision
        # the agent acks; the resumed action completes normally
        manager.fence_node(2, ttl_s=60.0)
        brain_b.run_cycle(now=T0 + 5)
        assert brain_b.optimizer.in_flight is None

    def test_failover_stale_in_flight_is_abandoned_safely(self):
        """An in-flight action far past its deadline at replay time
        must be forced (fence fallback), not acted on as if fresh."""
        manager = completed_world([0, 1, 2])
        brain_a = make_brain(manager)
        brain_a.optimizer._in_flight = BrainDecision(
            decision_id=7, action=ACTION_DRAIN_REPLACE,
            reason="straggler:9.0x", node=1, from_world=3,
            to_world=2, made_at=T0,
        )
        state = brain_a.export_state()
        brain_b = make_brain(manager)
        brain_b.restore_state(state)
        brain_b.run_cycle(now=T0 + 100_000.0)
        assert brain_b.optimizer.in_flight is None
        assert brain_b.optimizer.last_decision.decision_id == 7
        assert 1 in manager.fenced_ranks()

    def test_directive_rides_waiting_num_response_once(self):
        """Servicer piggyback: the pending directive is delivered on
        the node's own waiting-num poll, exactly once, and other
        nodes never see it."""
        from dlrover_tpu.master.servicer import MasterServicer

        manager = completed_world([0, 1, 2])
        health = FakeHealth()
        health.straggler_list = [(2, 4.0)]
        brain = make_brain(manager, health)
        for i in range(3):
            brain.run_cycle(now=T0 + i)
        servicer = MasterServicer(
            rdzv_managers={
                RendezvousName.ELASTIC_TRAINING: manager
            },
            brain=brain,
        )
        req = msg.WaitingNodeNumRequest()
        other = servicer._get_waiting_num(req, node_id=0)
        assert getattr(other, "action", "") == ""
        res = servicer._get_waiting_num(req, node_id=2)
        assert res.action == "drain"
        assert res.action_id == 1
        assert "straggler" in res.action_reason
        again = servicer._get_waiting_num(req, node_id=2)
        assert getattr(again, "action", "") == ""  # consumed

    def test_drain_defers_pod_removal_until_drain_concludes(self):
        """The pod-side leg must not race the cooperative drain: the
        scaler sees NOTHING at begin() (deleting the pod would
        SIGTERM the agent before the directive's next-poll delivery);
        the migrate plan lands only once the node is fenced/out — and
        only once, even across a resumed check."""
        from dlrover_tpu.master.scaler import InMemoryScaler

        class NamedJobManager:
            def get_running_nodes(self):
                class N:
                    def __init__(self, i):
                        self.rank_index = i
                        self.id = i
                        self.name = f"job-worker-{i}"

                return [N(i) for i in range(3)]

        manager = completed_world([0, 1, 2])
        health = FakeHealth()
        health.straggler_list = [(2, 4.0)]
        scaler = InMemoryScaler()
        executor = BrainExecutor(
            rdzv_manager=manager,
            directives=NodeDirectives(),
            job_manager=NamedJobManager(),
            scaler=scaler,
        )
        brain = BrainAutoScaler(
            ObservatoryBrainOptimizer(
                cooldown_s=10.0, sustain_cycles=2
            ),
            executor,
            health_engine=health,
            interval=3600.0,
        )
        for i in range(3):
            brain.run_cycle(now=T0 + i)
        decision = brain.optimizer.in_flight
        assert decision is not None
        assert decision.to_world == 3  # replace (launch capacity)
        assert not scaler.plans, "begin() must not touch the scaler"
        manager.fence_node(2, ttl_s=60.0)
        brain.run_cycle(now=T0 + 3)
        assert brain.optimizer.in_flight is None
        assert len(scaler.plans) == 1
        assert "job-worker-2" in scaler.plans[0].migrate_nodes
        # idempotence: a second check for the same decision is a no-op
        executor.check(decision)
        assert len(scaler.plans) == 1

    def test_scaler_grow_executes_plan(self):
        from dlrover_tpu.master.scaler import InMemoryScaler

        manager = completed_world([0, 1], max_nodes=3)
        scaler = InMemoryScaler()
        brain = make_brain(manager)
        brain.set_scaler(scaler)
        for i in range(4):
            brain.run_cycle(now=T0 + i)
        assert brain.optimizer.in_flight is not None
        assert brain.optimizer.in_flight.action == ACTION_GROW
        assert scaler.plans, "grow must reach the scaler"
        plan = scaler.plans[-1]
        assert plan.node_group_resources["worker"]["count"] == 3


class TestSeedPin:
    """DLROVER_TPU_BRAIN=0 reproduces the seed auto-scaler exactly."""

    def _distributed_master(self, monkeypatch, brain: str):
        from dlrover_tpu.common.env import get_free_port
        from dlrover_tpu.master.master import DistributedJobMaster
        from dlrover_tpu.master.scaler import InMemoryScaler

        monkeypatch.setenv("DLROVER_TPU_BRAIN", brain)
        return DistributedJobMaster(
            get_free_port(), 2, scaler=InMemoryScaler(), max_workers=4
        )

    def test_kill_switch_restores_seed_wiring(self, monkeypatch):
        from dlrover_tpu.master.resource_optimizer import (
            LocalAllreduceOptimizer,
        )

        master = self._distributed_master(monkeypatch, "0")
        assert master.brain is None
        assert isinstance(master.auto_scaler, AllreduceAutoScaler)
        assert isinstance(
            master.auto_scaler._optimizer, LocalAllreduceOptimizer
        )

    def test_brain_replaces_seed_loop(self, monkeypatch):
        master = self._distributed_master(monkeypatch, "1")
        assert isinstance(master.brain, BrainAutoScaler)
        assert master.auto_scaler is None
        assert master.brain.executor.can_launch

    def test_kill_switch_keeps_directives_off_the_wire(
        self, monkeypatch
    ):
        from dlrover_tpu.master.servicer import MasterServicer

        manager = completed_world([0, 1])
        servicer = MasterServicer(
            rdzv_managers={
                RendezvousName.ELASTIC_TRAINING: manager
            },
            brain=None,  # what BRAIN=0 wires
        )
        res = servicer._get_waiting_num(
            msg.WaitingNodeNumRequest(), node_id=0
        )
        assert res.action == ""
        assert res.action_id == 0


class FlakyOptimizer:
    def __init__(self, exc=RuntimeError("boom")):
        self.exc = exc
        self.calls = 0

    def generate_plan(self, stage):
        self.calls += 1
        raise self.exc


class TestSeedLoopSatellites:
    def test_cycle_errors_counted_and_traceback_throttled(
        self, monkeypatch
    ):
        """Every failing cycle ticks the counter; the traceback is
        written for the first one and then once a cooldown.  The
        throttle runs on an injected clock that starts 10 s after
        "boot": the monotonic clock counts from there, and a master
        on a machine younger than the cooldown wrote no traceback at
        all while the throttle's first stamp was 0.0 (the red run on
        record: a tier-1 run inside the machine's first 300 s)."""
        import logging

        from dlrover_tpu.master import auto_scaler as mod
        from dlrover_tpu.master.scaler import InMemoryScaler
        from dlrover_tpu.observability.metrics import get_registry

        registry = get_registry()
        key = "dlrover_tpu_autoscale_errors"
        before = registry._metrics.get(key, 0.0)
        # the real loop: three failing cycles reach the accounting
        # (the deadline only keeps a wedged loop from hanging the run)
        auto = AllreduceAutoScaler(
            FlakyOptimizer(), InMemoryScaler(), interval=0.01
        )
        auto.start()
        deadline = time.time() + 60.0
        while auto.cycle_errors < 3 and time.time() < deadline:
            time.sleep(0.01)
        auto.stop()
        assert auto.cycle_errors >= 3
        looped = auto.cycle_errors
        assert registry._metrics.get(key, 0.0) >= before + looped

        # the throttle, on the injected clock
        records = []

        class _Keep(logging.Handler):
            def emit(self, record):
                records.append(record)

        keep = _Keep()
        mod.logger.addHandler(keep)
        clock = [10.0]

        class _Clock:
            """The module's ``time``, but for ``monotonic``."""

            def __getattr__(self, name):
                return getattr(time, name)

            def monotonic(self):
                return clock[0]

        monkeypatch.setattr(mod, "time", _Clock())
        fresh = AllreduceAutoScaler(
            FlakyOptimizer(), InMemoryScaler(), interval=0.01
        )
        try:
            for _ in range(3):
                fresh._on_cycle_error(RuntimeError("boom"))
                clock[0] += 1.0
            assert fresh._last_error_log == 10.0  # advanced once
            assert [bool(r.exc_info) for r in records] == [
                True, False, False,
            ]
            clock[0] = 10.0 + fresh.ERROR_LOG_COOLDOWN_S
            fresh._on_cycle_error(RuntimeError("boom"))
            assert fresh._last_error_log == clock[0]
            assert bool(records[-1].exc_info)
        finally:
            mod.logger.removeHandler(keep)
        assert fresh.cycle_errors == 4
        after = registry._metrics.get(key, 0.0)
        assert after >= before + looped + 4

    def test_stop_joins_the_loop_thread(self):
        from dlrover_tpu.master.scaler import InMemoryScaler

        auto = AllreduceAutoScaler(
            FlakyOptimizer(), InMemoryScaler(), interval=0.01
        )
        auto.start()
        thread = auto._thread
        assert thread is not None and thread.is_alive()
        auto.stop()
        assert not thread.is_alive()

    def test_brain_stop_joins(self):
        manager = completed_world([0, 1])
        brain = make_brain(manager)
        brain._interval = 0.01
        brain.start()
        thread = brain._thread
        assert thread.is_alive()
        brain.stop()
        assert not thread.is_alive()

    def test_start_stop_restart(self):
        """stop() must leave the scaler restartable (the master may
        hand components over)."""
        from dlrover_tpu.master.scaler import InMemoryScaler

        auto = AllreduceAutoScaler(
            FlakyOptimizer(), InMemoryScaler(), interval=0.01
        )
        auto.start()
        auto.stop()
        auto.start()
        assert auto._thread is not None and auto._thread.is_alive()
        auto.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
