"""The zero-copy RLHF flywheel (ISSUE 20).

Covers every leg of ``rl/flywheel.py`` + ``master/flywheel_operator``
and the machinery they ride:

- the generation side-segment (publish/peek, torn publish never
  advances it, restart-safe re-attach);
- logprob capture through the scheduler and the serving engine:
  on when asked for, and without it the plain programs and empty
  logprobs;
- the trajectory stream: exactly-once by req-id (journal survives a
  consumer restart), staleness drop/tag, schema versioning;
- the Brain arbiter: sustain/cooldown/hysteresis, the min-train-world
  floor, journal round-trip and in-flight resume after failover;
- the trainer bridge: streamed logprobs replace the actor recompute
  bitwise.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.agent.ckpt_shm import (  # noqa: E402
    SharedMemoryHandler,
)
from dlrover_tpu.master.flywheel_operator import (  # noqa: E402
    FlywheelArbiter,
    FlywheelOperator,
    FlywheelSignals,
)
from dlrover_tpu.rl.flywheel import (  # noqa: E402
    Trajectory,
    TrajectorySink,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG_KW = dict(
    vocab_size=64,
    dim=16,
    n_layers=1,
    n_heads=2,
    n_kv_heads=1,
    mlp_dim=32,
    max_seq_len=64,
    remat="none",
)


def _tiny_params(seed: int = 0):
    from dlrover_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**CFG_KW)
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def _flat_equal(a, b) -> bool:
    return set(a) == set(b) and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a
    )


# --------------------------------------------------------------------------
# generation side-segment
# --------------------------------------------------------------------------
class TestGenerationSegment:
    def test_publish_peek_roundtrip_and_save_never_bumps(self):
        cfg, params = _tiny_params()
        h = SharedMemoryHandler(
            rank=0, name=f"flygen-{os.getpid()}", host=True
        )
        try:
            assert h.peek_generation() == -1
            h.save_state(3, params)
            # save_state alone NEVER advances the generation — the
            # bump is the writer's explicit post-save commit point
            assert h.peek_generation() == -1
            h.publish_generation(3)
            assert h.peek_generation() == 3
            h.save_state(4, params)
            assert h.peek_generation() == 3
            h.publish_generation(4)
            assert h.peek_generation() == 4
        finally:
            h.close(unlink=True)

    def test_restarted_publisher_reattaches_live_segment(self):
        cfg, params = _tiny_params()
        name = f"flyre-{os.getpid()}"
        h = SharedMemoryHandler(rank=0, name=name, host=True)
        try:
            h.save_state(1, params)
            h.publish_generation(1)
            # a NEW handler (restarted trainer) publishes into the
            # already-existing segment without tripping on create
            h2 = SharedMemoryHandler(rank=0, name=name, host=False)
            h2.publish_generation(2)
            assert h.peek_generation() == 2
        finally:
            h.close(unlink=True)

    def test_unsized_segment_reads_as_not_yet_published(self):
        """A publisher creates the segment's file and sizes it a moment
        later: a reader that attaches in between finds an empty file
        (``mmap`` refuses it), which is "not there yet", not an error —
        and it sees the generation once the publisher is through."""
        name = f"flyempty-{os.getpid()}"
        h = SharedMemoryHandler(rank=0, name=name, host=True)
        path = os.path.join("/dev/shm", h._gen_name)
        try:
            os.close(os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600))
            assert os.path.getsize(path) == 0
            assert h.peek_generation() == -1
            assert h.peek_generation() == -1  # and attaches nothing
            os.unlink(path)
            h.publish_generation(5)
            assert h.peek_generation() == 5
        finally:
            if os.path.exists(path):
                os.unlink(path)
            h.close(unlink=True)

    @pytest.mark.timeout(300)
    def test_torn_publish_serves_previous_generation(self):
        """Satellite 3: a publisher SIGKILLed inside ``save_state``
        (the ``mid_weight_publish`` hook — after the leaves land,
        before the meta flips) leaves readers on the previous
        snapshot bitwise and never advances the generation."""
        cfg, params = _tiny_params(seed=0)
        name = f"flytorn-{os.getpid()}"
        h = SharedMemoryHandler(rank=0, name=name, host=True)
        try:
            h.save_state(1, params)
            h.publish_generation(1)
            step_before, flat_before = h.load_state()
            assert step_before == 1
            child = subprocess.run(
                [sys.executable, "-c", (
                    "import sys\n"
                    f"sys.path.insert(0, {REPO!r})\n"
                    "import jax\n"
                    "from dlrover_tpu.models.llama import ("
                    "LlamaConfig, init_params)\n"
                    "from dlrover_tpu.agent.ckpt_shm import ("
                    "SharedMemoryHandler)\n"
                    f"cfg = LlamaConfig(**{CFG_KW!r})\n"
                    "params = init_params(jax.random.PRNGKey(9), cfg)\n"
                    f"h = SharedMemoryHandler(rank=0, name={name!r})\n"
                    "h.save_state(2, params)\n"
                    "print('UNREACHABLE')\n"
                )],
                env=dict(
                    os.environ,
                    JAX_PLATFORMS="cpu",
                    DLROVER_TPU_FAULT_PLAN=json.dumps({
                        "faults": [{
                            "kind": "kill",
                            "phase": "mid_weight_publish",
                        }]
                    }),
                ),
                capture_output=True,
                text=True,
                timeout=240,
            )
            assert child.returncode == -9, child.stdout + child.stderr
            assert "UNREACHABLE" not in child.stdout
            # readers: same generation, same step, same bytes as
            # before the kill — the torn seed-9 write is invisible
            assert h.peek_generation() == 1
            step_after, flat_after = h.load_state()
            assert step_after == 1
            assert _flat_equal(flat_before, flat_after)
        finally:
            h.close(unlink=True)


# --------------------------------------------------------------------------
# scheduler-level: logprob capture, and the plain closures without it
# --------------------------------------------------------------------------
class TestSchedulerCapture:
    @pytest.mark.timeout(600)
    def test_capture_matches_recompute_and_off_pins_empty(self):
        from dlrover_tpu.models.llama import forward
        from dlrover_tpu.rl.scheduler import (
            ContinuousBatchingScheduler,
            SchedulerConfig,
        )
        from dlrover_tpu.rl.trainer import token_logprobs

        cfg, params = _tiny_params()
        sched_kw = dict(
            max_slots=2, block_size=8, num_blocks=32,
            max_seq_len=32, prefill_chunk=8, temperature=0.7,
        )
        prompt = np.array([5, 9, 2, 11], np.int32)

        def run(capture: bool):
            sch = ContinuousBatchingScheduler(
                cfg, SchedulerConfig(**sched_kw),
                capture_logprobs=capture,
            )
            sch.sync_weights(params)
            rid = sch.submit(prompt, max_new=6, seed=3)
            for _ in range(500):
                for res in sch.step():
                    if res.req_id == rid:
                        return res
            raise AssertionError("request never completed")

        off = run(False)
        on = run(True)
        # capture OFF is today's scheduler: no logprobs surface
        assert off.logprobs.size == 0
        # and the sampled tokens are identical either way (capture
        # must not perturb sampling)
        np.testing.assert_array_equal(off.tokens, on.tokens)
        assert on.logprobs.shape == (on.new_tokens,)
        # captured values == the trainer's own recompute (the whole
        # point: streamed old_logp replaces the actor forward)
        tokens = on.tokens[None].astype(np.int32)
        logits = jax.jit(
            lambda p, t: forward(p, t, cfg, attention_fn=None)
        )(params, tokens)
        ref = np.asarray(token_logprobs(logits, tokens))[0]
        plen = prompt.size
        np.testing.assert_allclose(
            on.logprobs,
            ref[plen - 1 : plen - 1 + on.new_tokens],
            rtol=2e-4, atol=2e-4,
        )

    def test_resume_longer_than_budget_rejected(self):
        from dlrover_tpu.rl.scheduler import (
            ContinuousBatchingScheduler,
            SchedulerConfig,
        )

        cfg, params = _tiny_params()
        sch = ContinuousBatchingScheduler(
            cfg,
            SchedulerConfig(
                max_slots=2, block_size=8, num_blocks=32,
                max_seq_len=32, prefill_chunk=8,
            ),
        )
        sch.sync_weights(params)
        with pytest.raises(ValueError, match="resume"):
            sch.submit(
                np.array([1, 2, 3], np.int32),
                max_new=4,
                resume_tokens=np.array([7, 8, 9, 10], np.int32),
            )


# --------------------------------------------------------------------------
# engine-level: capture and draft plumbing (no replicas)
# --------------------------------------------------------------------------
class TestEngineCaptureAndDraft:
    def _engine(self, name: str, **kw):
        from dlrover_tpu.rl.generation_service import ServingEngine

        return ServingEngine(
            factory=(
                "dlrover_tpu.rl.generation_service:"
                "tiny_llama_factory"
            ),
            factory_kwargs=dict(CFG_KW, **kw.pop("extra_cfg", {})),
            max_new_tokens=4,
            name=name,
            num_replicas=0,
            **kw,
        )

    def test_capture_and_draft_are_only_what_was_asked_for(self):
        """An engine asked for neither: the worker spec carries no
        flywheel key and no draft model (the replica compiles the
        plain programs), nothing is captured — and its publishes bump
        the generation segment all the same, so its replicas adopt
        without a meta RPC.  Asked for a draft alone, the spec says so
        with capture off."""
        eng = self._engine(f"flyplain-{os.getpid()}")
        try:
            assert eng._capture is False and not eng._draft_mode
            assert "flywheel" not in eng._spec
            assert "draft" not in eng._spec["factory_kwargs"]
            assert eng._shm.peek_generation() == -1
            cfg, params = _tiny_params()
            eng.sync_weights(params)
            assert eng._shm.peek_generation() == 1
        finally:
            eng.close()
        drafted = self._engine(
            f"flydr-{os.getpid()}",
            extra_cfg={"draft": dict(CFG_KW, dim=8)},
        )
        try:
            assert drafted._spec["flywheel"] == {"capture": False}
            assert drafted._spec["factory_kwargs"]["draft"]["dim"] == 8
        finally:
            drafted.close()

    def test_flywheel_on_publishes_generation(self):
        eng = self._engine(
            f"flyon-{os.getpid()}", capture_logprobs=True
        )
        try:
            assert eng._capture is True
            assert eng._spec["flywheel"] == {"capture": True}
            cfg, params = _tiny_params()
            eng.sync_weights(params)
            assert eng._shm.peek_generation() == 1
            eng.sync_weights(params)
            assert eng._shm.peek_generation() == 2
        finally:
            eng.close()

    def test_draft_mode_requires_draft_params_both_ways(self):
        eng = self._engine(
            f"flydraft-{os.getpid()}",
            extra_cfg={"draft": dict(CFG_KW, dim=8)},
        )
        try:
            cfg, params = _tiny_params()
            with pytest.raises(ValueError, match="draft"):
                eng.sync_weights(params)  # draft mode, no drafter
        finally:
            eng.close()
        eng2 = self._engine(f"flynod-{os.getpid()}")
        try:
            cfg, params = _tiny_params()
            with pytest.raises(ValueError, match="draft"):
                eng2.sync_weights(params, draft_params=params)
        finally:
            eng2.close()


# --------------------------------------------------------------------------
# trajectory stream: exactly-once + staleness + journal
# --------------------------------------------------------------------------
class _FakeEngine:
    def __init__(self):
        self._version = 0

    def sync_weights(self, params, draft_params=None):
        self._version += 1
        return 0.0


class TestTrajectoryStream:
    def _coordinator(self, tag=0, **kw):
        from dlrover_tpu.rl.flywheel import FlywheelCoordinator

        return FlywheelCoordinator(
            _FakeEngine(), max_total=32,
            # short name: the ring handshake is an AF_UNIX socket
            # under the per-test socket dir, and sun_path is 108 bytes
            name=f"ft{tag}",
            ring_slots=8, **kw,
        )

    def _result(self, n_prompt=4, n_new=5):
        return {
            "tokens": np.arange(n_prompt + n_new, dtype=np.int32),
            "new_tokens": n_new,
            "logprobs": np.linspace(
                -0.5, -2.5, n_new
            ).astype(np.float32),
            "version": 1,
            "finish_reason": "length",
        }

    def test_offer_drain_roundtrip_fidelity(self):
        co = self._coordinator(tag=1)
        try:
            co.publish({"w": np.ones((3,), np.float32)})
            prompt = np.arange(4, dtype=np.int32)
            res = self._result()
            assert co.offer_result(11, prompt, res, seed=42)
            out = co.drain()
            assert len(out) == 1
            t = out[0]
            assert t.req_id == 11
            assert t.prompt_len == 4 and t.new_tokens == 5
            assert t.generation == 1 and t.seed == 42
            assert not t.stale and t.lag == 0
            np.testing.assert_array_equal(t.tokens, res["tokens"])
            np.testing.assert_allclose(
                t.logprobs, res["logprobs"], rtol=1e-6
            )
        finally:
            co.close()

    def test_duplicate_req_id_refused(self):
        co = self._coordinator(tag=2)
        try:
            co.publish({"w": np.ones((3,), np.float32)})
            prompt = np.arange(4, dtype=np.int32)
            res = self._result()
            assert co.offer_result(7, prompt, res)
            assert len(co.drain()) == 1
            # the drain/crash replay race: same req-id again
            assert co.offer_result(7, prompt, res)
            assert co.drain() == []
            assert co.stats.duplicates == 1
        finally:
            co.close()

    def test_stale_drop_consumes_exactly_once(self):
        co = self._coordinator(tag=3, staleness="drop", max_lag=1)
        try:
            co.generation = 5
            prompt = np.arange(4, dtype=np.int32)
            res = self._result()  # sampled at generation 1: lag 4
            assert co.offer_result(8, prompt, res)
            assert co.drain() == []
            assert co.stats.staleness_dropped == 1
            # dropped != forgotten: the id is consumed, a replay of
            # it must dedup rather than re-enter the staleness path
            assert co.offer_result(8, prompt, res)
            assert co.drain() == []
            assert co.stats.duplicates == 1
            assert co.stats.staleness_dropped == 1
        finally:
            co.close()

    def test_stale_tag_keeps_trajectory_marked(self):
        co = self._coordinator(tag=4, staleness="tag", max_lag=0)
        try:
            co.generation = 3
            prompt = np.arange(4, dtype=np.int32)
            assert co.offer_result(9, prompt, self._result())
            out = co.drain()
            assert len(out) == 1
            assert out[0].stale and out[0].lag == 2
            assert co.stats.staleness_tagged == 1
        finally:
            co.close()

    def test_journal_survives_consumer_restart(self, tmp_path):
        jp = str(tmp_path / "seen.journal")
        s1 = TrajectorySink(
            policy="drop", max_lag=10, journal_path=jp
        )
        t = Trajectory(
            req_id=21, tokens=np.arange(6, dtype=np.int32),
            prompt_len=2, new_tokens=4,
            logprobs=np.zeros(4, np.float32), generation=1,
        )
        assert s1.accept(t, 1) is not None
        s1.close()
        # restarted consumer, same journal: the id is already spent
        s2 = TrajectorySink(
            policy="drop", max_lag=10, journal_path=jp
        )
        t2 = Trajectory(
            req_id=21, tokens=np.arange(6, dtype=np.int32),
            prompt_len=2, new_tokens=4,
            logprobs=np.zeros(4, np.float32), generation=1,
        )
        assert s2.accept(t2, 1) is None
        assert s2.stats.duplicates == 1
        s2.close()

    def test_schema_mismatch_raises(self):
        from dlrover_tpu.rl import flywheel as fw

        co = self._coordinator(tag=5)
        try:
            prompt = np.arange(4, dtype=np.int32)
            assert co.offer_result(3, prompt, self._result())
            # corrupt the schema stamp in flight
            msg = co._ring.try_get()
            assert msg is not None
            msg = {k: np.array(v) for k, v in msg.items()}
            msg["meta"][6] = fw.TRAJ_SCHEMA_VERSION + 1
            assert co._ring.try_put(msg, timeout=1.0)
            with pytest.raises(RuntimeError, match="schema"):
                co.drain()
        finally:
            co.close()


# --------------------------------------------------------------------------
# Brain arbiter + operator
# --------------------------------------------------------------------------
class TestFlywheelArbiter:
    def _arbiter(self, **kw):
        base = dict(
            lend_q=4.0, reclaim_q=0.5, min_train_world=1,
            sustain_cycles=3, cooldown_s=10.0,
        )
        base.update(kw)
        return FlywheelArbiter(**base)

    def test_lend_needs_sustained_pressure(self):
        arb = self._arbiter()
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        assert arb.decide(busy, now=100.0) is None
        assert arb.decide(busy, now=101.0) is None
        d = arb.decide(busy, now=102.0)
        assert d is not None and d.action == "lend"
        assert d.from_world == 4 and d.to_world == 3
        assert d.from_replicas == 2 and d.to_replicas == 3

    def test_one_blip_resets_the_streak(self):
        arb = self._arbiter()
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        idle = FlywheelSignals(
            queue_depth=0, serve_replicas=2, train_world=4
        )
        arb.decide(busy, now=100.0)
        arb.decide(busy, now=101.0)
        arb.decide(idle, now=102.0)  # pressure vanished for a cycle
        assert arb.decide(busy, now=103.0) is None
        assert arb.decide(busy, now=104.0) is None
        assert arb.decide(busy, now=105.0) is not None

    def test_single_in_flight_and_completion_anchored_cooldown(self):
        arb = self._arbiter()
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        d = None
        for i in range(3):
            d = arb.decide(busy, now=100.0 + i)
        assert d is not None
        assert arb.decide(busy, now=103.0) is None  # one in flight
        arb.complete("done", now=110.0)
        assert arb.lent == 1
        # cooldown runs from COMPLETION (110), not decision (102)
        for i in range(5):
            assert arb.decide(busy, now=112.0 + i) is None
        assert arb.decide(busy, now=121.0) is not None

    def test_hysteresis_doubles_the_flip_cooldown(self):
        arb = self._arbiter(sustain_cycles=1)
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        idle = FlywheelSignals(
            queue_depth=0, serve_replicas=3, train_world=3
        )
        assert arb.decide(busy, now=100.0) is not None
        arb.complete("done", now=100.0)
        # same-direction cooldown would clear at 110; the FLIP to
        # reclaim must wait 2x (120)
        assert arb.decide(idle, now=115.0) is None
        assert arb.decide(idle, now=121.0) is not None

    def test_min_train_world_floor(self):
        arb = self._arbiter(sustain_cycles=1, min_train_world=2)
        floor = FlywheelSignals(
            queue_depth=50, serve_replicas=1, train_world=2
        )
        assert arb.decide(floor, now=100.0) is None

    def test_reclaim_only_takes_back_lent_chips(self):
        arb = self._arbiter(sustain_cycles=1, cooldown_s=0.0)
        idle = FlywheelSignals(
            queue_depth=0, serve_replicas=4, train_world=2
        )
        # nothing lent: an idle fleet is NOT the flywheel's to shrink
        for i in range(5):
            assert arb.decide(idle, now=100.0 + i) is None

    def test_abandoned_outcome_moves_no_chips(self):
        arb = self._arbiter(sustain_cycles=1)
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        assert arb.decide(busy, now=100.0) is not None
        arb.complete("abandoned", now=100.0)
        assert arb.lent == 0

    def test_state_round_trip(self):
        arb = self._arbiter(sustain_cycles=1)
        busy = FlywheelSignals(
            queue_depth=20, serve_replicas=2, train_world=4
        )
        d = arb.decide(busy, now=100.0)
        assert d is not None
        state = arb.export_state()
        arb2 = self._arbiter()
        arb2.restore_state(state)
        assert arb2.export_state() == state
        assert arb2.in_flight is not None
        assert arb2.in_flight.decision_id == d.decision_id


class TestFlywheelOperator:
    def _operator(self, lend=None, reclaim=None, **arb_kw):
        base = dict(
            lend_q=4.0, reclaim_q=0.5, sustain_cycles=1,
            cooldown_s=0.0,
        )
        base.update(arb_kw)
        return FlywheelOperator(
            lend_fn=lend or (lambda d: True),
            reclaim_fn=reclaim or (lambda d: True),
            arbiter=FlywheelArbiter(**base),
        )

    def test_evaluate_executes_and_journals(self):
        rows = []
        calls = []
        op = self._operator(
            lend=lambda d: calls.append(d.decision_id) or True
        )
        op.set_journal(lambda k, p: rows.append((k, p)))
        out = op.evaluate(
            FlywheelSignals(
                queue_depth=20, serve_replicas=2, train_world=4
            ),
            now=100.0,
        )
        assert out == "done"
        assert calls == [1]
        kinds = [k for k, _ in rows]
        assert "decision" in kinds
        assert "execute" in kinds
        assert "state" in kinds  # every transition snapshots state
        assert op.arbiter.lent == 1

    def test_failover_resumes_in_flight_decision(self):
        # master 1 decides, then dies before executing
        arb = FlywheelArbiter(
            lend_q=4.0, reclaim_q=0.5, sustain_cycles=1,
            cooldown_s=0.0,
        )
        d = arb.decide(
            FlywheelSignals(
                queue_depth=20, serve_replicas=2, train_world=4
            ),
            now=100.0,
        )
        snap = arb.export_state()
        # master 2 restores and resumes the SAME decision id
        calls = []
        op = self._operator(
            lend=lambda dec: calls.append(dec.decision_id) or True
        )
        op.restore_state(snap)
        assert op.resume_in_flight() == "done"
        assert calls == [d.decision_id]
        assert op.arbiter.in_flight is None
        assert op.arbiter.lent == 1

    def test_executor_crash_abandons_instead_of_wedging(self):
        def boom(decision):
            raise RuntimeError("boom")

        op = self._operator(lend=boom)
        out = op.evaluate(
            FlywheelSignals(
                queue_depth=20, serve_replicas=2, train_world=4
            ),
            now=100.0,
        )
        assert out == "abandoned"
        assert op.arbiter.in_flight is None
        assert op.arbiter.lent == 0


# --------------------------------------------------------------------------
# trainer bridge: streamed logprobs replace the actor recompute
# --------------------------------------------------------------------------
class TestTrainerBridge:
    def _trainer(self):
        import jax.numpy as jnp
        import optax

        from dlrover_tpu.models.llama import (
            LlamaConfig,
            forward,
            init_params,
            param_logical_axes,
        )
        from dlrover_tpu.rl.config import RLConfig
        from dlrover_tpu.rl.engine import ModelEngine
        from dlrover_tpu.rl.inference import KVCacheBackend
        from dlrover_tpu.rl.trainer import (
            RLHFTrainer,
            actor_ppo_loss,
            critic_value_loss,
        )

        cfg = LlamaConfig(**CFG_KW)

        def actor_forward(p, tokens):
            return forward(p, tokens, cfg, attention_fn=None)

        config = RLConfig.from_dict({
            "roles": {
                "actor": {"strategy": {"data": 8, "remat": "none"}},
                "critic": {"strategy": {"data": 8, "remat": "none"}},
            },
            "ppo": {"rollout_batch": 4, "ppo_epochs": 1},
        })
        engine = ModelEngine(config)
        engine.build_role(
            "actor",
            loss_fn=lambda p, b: actor_ppo_loss(
                actor_forward(p, b["tokens"]), b
            ),
            optimizer=optax.adam(1e-4),
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
        )

        def critic_init(rng):
            return {
                "emb": jax.random.normal(
                    rng, (cfg.vocab_size, 8), jnp.float32
                ) * 0.1,
                "w": jnp.zeros((8,), jnp.float32),
            }

        def critic_value(p, tokens):
            return jnp.einsum(
                "bse,e->bs", p["emb"][tokens], p["w"]
            )

        engine.build_role(
            "critic",
            loss_fn=lambda p, b: critic_value_loss(
                critic_value(p, b["tokens"]), b
            ),
            optimizer=optax.adam(1e-3),
            init_params_fn=critic_init,
            param_axes={"emb": (None, None), "w": (None,)},
        )
        engine.init_role_state("actor", jax.random.PRNGKey(0))
        engine.init_role_state("critic", jax.random.PRNGKey(1))
        backend = KVCacheBackend(
            cfg, max_new_tokens=4, temperature=1.0
        )
        return RLHFTrainer(
            config, engine, backend,
            actor_forward=actor_forward,
            critic_value=critic_value,
            reward_fn=lambda tokens: np.asarray(
                tokens[:, -1] % 3, np.float32
            ),
            prompt_len=4,
        )

    @pytest.mark.timeout(600)
    def test_streamed_logprobs_skip_the_actor_recompute(self):
        trainer = self._trainer()
        actor_params = trainer.engine.states["actor"]["params"]
        rng = np.random.default_rng(5)
        b, plen, new = 4, 4, 5
        tokens = rng.integers(
            0, CFG_KW["vocab_size"], (b, plen + new)
        ).astype(np.int32)
        full_lp = np.asarray(
            trainer._logp_fn(actor_params, tokens)
        )
        trajs = [
            Trajectory(
                req_id=i,
                tokens=tokens[i],
                prompt_len=plen,
                new_tokens=new,
                logprobs=full_lp[i, plen - 1 : plen - 1 + new],
                generation=1,
            )
            for i in range(b)
        ]
        calls = []
        orig = trainer._logp_fn
        trainer._logp_fn = lambda p, t: calls.append(1) or orig(p, t)
        stats = trainer.experience_from_trajectories(trajs)
        assert stats["samples"] == b
        # ONE forward: the frozen ref policy.  The actor recompute —
        # the hop the stream exists to delete — never runs.
        assert len(calls) == 1
        sample = trainer.buffer._items[0]
        mask = sample["mask"] > 0
        np.testing.assert_allclose(
            sample["old_logp"][mask], full_lp[0][mask],
            rtol=1e-6, atol=1e-6,
        )

    @pytest.mark.timeout(600)
    def test_nan_gaps_fall_back_to_one_recompute(self):
        trainer = self._trainer()
        rng = np.random.default_rng(6)
        b, plen, new = 2, 4, 5
        tokens = rng.integers(
            0, CFG_KW["vocab_size"], (b, plen + new)
        ).astype(np.int32)
        trajs = [
            Trajectory(
                req_id=i, tokens=tokens[i], prompt_len=plen,
                new_tokens=new,
                logprobs=np.full((new,), np.nan, np.float32),
                generation=1,
            )
            for i in range(b)
        ]
        calls = []
        orig = trainer._logp_fn
        trainer._logp_fn = lambda p, t: calls.append(1) or orig(p, t)
        trainer.experience_from_trajectories(trajs)
        # actor recompute + ref forward
        assert len(calls) == 2
        actor_params = trainer.engine.states["actor"]["params"]
        full_lp = np.asarray(orig(actor_params, tokens))
        sample = trainer.buffer._items[0]
        mask = sample["mask"] > 0
        np.testing.assert_allclose(
            sample["old_logp"][mask], full_lp[0][mask],
            rtol=1e-6, atol=1e-6,
        )

    @pytest.mark.timeout(600)
    def test_make_experience_is_reproducible(self):
        """The in-process rollout path reads no state of the process
        beside its arguments: two trainers built alike, given the same
        prompts and key, fill identical buffers."""

        def run():
            trainer = self._trainer()
            prompts = np.tile(
                np.arange(4, dtype=np.int32)[None], (4, 1)
            )
            trainer.make_experience(
                jax.numpy.asarray(prompts), jax.random.PRNGKey(7)
            )
            return trainer.buffer._items

        first, second = run(), run()
        assert len(first) == len(second) > 0
        for a, b in zip(first, second):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
