"""The serving plane explains itself (ISSUE 16): per-request
lifecycle tracing, TTFT/TBT SLO histograms, and the replica-health
observatory.

Contracts pinned here:

- every completed request gets a ``serve_request`` parent span with
  the full identity/SLO/efficiency label set, and a preempted request
  tells its WHOLE life (queue_wait -> admit -> preempt -> resume ->
  serve_request, one req_id) that survives the Perfetto export;
- ``record_serving_latency`` fills per-replica log-bucketed
  histograms rendered as ``_bucket``/``_sum``/``_count``;
- ``retire_series`` drops a dead replica's gauges AND histograms (a
  frozen last value reads as a live replica), and the dispatcher
  actually calls it when a replica dies;
- the shm ring refuses a mixed-version payload with a typed error
  naming both versions instead of misparsing it;
- ``ServingHealthEngine`` derives slo_straggler / dead_air /
  kv_pressure / preempt_storm verdicts with streak+cooldown
  discipline and emits the labeled instants — through the engine, a
  replica slowed and a replica wedged by the ``faults=`` seam are both
  named within three derivation intervals;
- every ``prefill`` and ``preempt`` record names its request.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.observability.events import (  # noqa: E402
    EventLogger,
    export_chrome_trace,
    read_events,
    set_default_event_logger,
)
from dlrover_tpu.observability.metrics import (  # noqa: E402
    MetricsRegistry,
    record_serving_latency,
    set_default_registry,
)
from dlrover_tpu.observability.health import (  # noqa: E402
    ServingHealthEngine,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

CFG = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none", dtype=jnp.float32,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)

SERVE_CFG_KW = dict(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=64, remat="none", dtype="float32",
)

SERVE_REQUEST_LABELS = {
    "req_id", "replica", "prompt_tokens", "gen_tokens",
    "ttft_s", "tbt_p99_s", "preempts", "prefix_hit_blocks",
}

PR14_STATUS_KEYS = {
    "replicas", "queue_depth", "completed",
    "p50_latency_s", "p99_latency_s", "version",
}


def _traced_scheduler(events_path, num_blocks=64, max_slots=4,
                      max_new_default=64):
    """A scheduler with the timeline on."""
    sch = ContinuousBatchingScheduler(
        CFG,
        SchedulerConfig(
            max_slots=max_slots, block_size=4, num_blocks=num_blocks,
            max_seq_len=64, prefill_chunk=8, temperature=0.0,
            max_new_default=max_new_default,
        ),
        events=EventLogger(path=str(events_path), job="obs-test"),
        replica="r-test",
    )
    sch.sync_weights(PARAMS)
    return sch


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e.get("name"), []).append(e)
    return out


class TestRequestTracing:
    def test_serve_request_spans_carry_full_label_set(self, tmp_path):
        """Every completed request produces one ``serve_request`` X
        record with the whole identity + SLO + efficiency label set,
        plus labeled queue_wait/admit children sharing its req_id."""
        ev = tmp_path / "events.jsonl"
        sch = _traced_scheduler(ev)
        ids = [
            sch.submit(
                np.arange(2 + i, dtype=np.int32), max_new=5,
                seed=70 + i,
            )
            for i in range(3)
        ]
        results = {r.req_id: r for r in sch.run()}
        assert set(results) == set(ids)

        names = _by_name(read_events(str(ev)))
        serve = [
            e for e in names.get("serve_request", ())
            if e.get("ph") == "X"
        ]
        assert len(serve) == len(ids)
        for e in serve:
            labels = e.get("labels") or {}
            missing = SERVE_REQUEST_LABELS - set(labels)
            assert not missing, f"serve_request missing {missing}"
            assert labels["replica"] == "r-test"
            assert labels["gen_tokens"] == 5
            assert labels["ttft_s"] >= 0.0
        traced_ids = {
            (e.get("labels") or {})["req_id"] for e in serve
        }
        assert traced_ids == set(ids)
        for child in ("queue_wait", "admit"):
            child_ids = {
                (e.get("labels") or {}).get("req_id")
                for e in names.get(child, ())
            }
            assert set(ids) <= child_ids, f"{child} missing req_ids"

    def test_result_stats_gain_slo_keys(self, tmp_path):
        sch = _traced_scheduler(tmp_path / "e.jsonl")
        rid = sch.submit(
            np.array([3, 1, 4], np.int32), max_new=6, seed=7
        )
        (res,) = list(sch.run())
        assert res.req_id == rid
        for key in ("tbt_p99_s", "queue_wait_s", "preempts",
                    "prefix_hit_blocks"):
            assert key in res.stats, res.stats
        assert res.stats["preempts"] == 0
        assert res.stats["queue_wait_s"] >= 0.0

    def test_preempted_request_tells_its_whole_life(
        self, tmp_path, monkeypatch
    ):
        """A pool sized at ~40% of worst-case demand under incremental
        allocation: growth hits the wall mid-decode and preempts —
        some request must trace queue_wait -> admit -> preempt ->
        resume -> serve_request under ONE req_id, and the file must
        survive the Perfetto export."""
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        ev = tmp_path / "events.jsonl"
        sch = _traced_scheduler(
            ev, num_blocks=26, max_slots=8, max_new_default=24,
        )
        rng = np.random.default_rng(29)
        for i in range(12):
            sch.submit(
                rng.integers(
                    0, 97, (int(rng.integers(4, 10)),)
                ).astype(np.int32),
                max_new=24, seed=300 + i,
            )
        results = list(sch.run())
        assert len(results) == 12
        preempted = [
            r for r in results if r.stats.get("preempts", 0) > 0
        ]
        assert preempted, "pool pressure produced no preemption"

        events = read_events(str(ev))
        by_req = {}
        for e in events:
            rid = (e.get("labels") or {}).get("req_id")
            if rid is not None:
                by_req.setdefault(rid, set()).add(e.get("name"))
        lifecycle = {
            "queue_wait", "admit", "preempt", "resume",
            "serve_request",
        }
        complete = [
            rid for rid, seen in by_req.items() if lifecycle <= seen
        ]
        assert complete, f"no complete lifecycle in {by_req}"
        # the preempted request's serve_request span still counts its
        # whole life: preempts label > 0
        serve = {
            (e.get("labels") or {})["req_id"]: e["labels"]
            for e in events
            if e.get("name") == "serve_request"
        }
        assert any(
            serve[rid]["preempts"] > 0 for rid in complete
        )
        trace_path = tmp_path / "trace.json"
        trace = export_chrome_trace(events, str(trace_path))
        assert trace["traceEvents"]
        payload = json.loads(trace_path.read_text())
        assert any(
            te.get("name") == "serve_request"
            for te in payload["traceEvents"]
        )


class TestIterationRecordsNameTheirRequest:
    def test_prefill_and_preempt_records_carry_req_id(
        self, tmp_path, monkeypatch
    ):
        """Under pool pressure every ``prefill`` chunk and every
        ``preempt`` names a request that was submitted; the chunks of
        a request add up to what was prefilled for it (its prompt,
        once more with its tail after each preemption), and its
        ``preempt`` records to the ``preempts`` its result reports."""
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        ev = tmp_path / "events.jsonl"
        sch = _traced_scheduler(
            ev, num_blocks=26, max_slots=8, max_new_default=24,
        )
        rng = np.random.default_rng(29)
        ids = [
            sch.submit(
                rng.integers(
                    0, 97, (int(rng.integers(4, 10)),)
                ).astype(np.int32),
                max_new=24, seed=300 + i,
            )
            for i in range(8)
        ]
        results = {r.req_id: r for r in sch.run()}
        assert set(results) == set(ids)
        names = _by_name(read_events(str(ev)))
        assert names.get("prefill") and names.get("preempt")
        chunks, evictions = {}, {}
        for e in names["prefill"]:
            rid = e["labels"]["req_id"]
            chunks[rid] = chunks.get(rid, 0) + e["labels"]["tokens"]
        for e in names["preempt"]:
            rid = e["labels"]["req_id"]
            evictions[rid] = evictions.get(rid, 0) + 1
        assert set(chunks) == set(ids)
        assert set(evictions) <= set(ids)
        assert sum(chunks.values()) == sch.total_prefill_tokens
        for rid, res in results.items():
            assert res.stats["preempts"] == evictions.get(rid, 0)
            prompt = res.tokens.size - res.new_tokens
            assert chunks[rid] >= prompt
            assert (chunks[rid] > prompt) == (rid in evictions)


class TestSLOHistograms:
    def test_record_serving_latency_fills_histograms(self, tmp_path):
        reg = MetricsRegistry(path=str(tmp_path / "m.prom"))
        set_default_registry(reg)
        try:
            for i in range(8):
                record_serving_latency(
                    replica="0", ttft_s=0.05 * (i + 1),
                    tbt_p99_s=0.01, e2e_s=0.5,
                    queue_wait_s=0.002,
                )
            record_serving_latency(replica="1", ttft_s=0.07)
            text = reg.render_text()
            for metric in (
                "dlrover_tpu_serving_ttft_seconds",
                "dlrover_tpu_serving_tbt_seconds",
                "dlrover_tpu_serving_e2e_seconds",
                "dlrover_tpu_serving_queue_wait_seconds",
            ):
                assert f"{metric}_bucket" in text, metric
                assert f"{metric}_sum" in text, metric
                assert f"{metric}_count" in text, metric
            ttft = reg.histogram(
                "dlrover_tpu_serving_ttft_seconds",
                labels={"replica": "0"},
            )
            assert ttft is not None and ttft.count == 8
            assert ttft.quantile(0.5) >= 0.1  # bucket upper bound
            assert reg.histogram(
                "dlrover_tpu_serving_ttft_seconds",
                labels={"replica": "1"},
            ).count == 1
        finally:
            set_default_registry(MetricsRegistry())

    def test_concurrent_observe_and_scrape(self, tmp_path):
        """Satellite 4: writers observing into one histogram family
        while a reader scrapes — no exception, no lost observation,
        every rendered exposition internally consistent."""
        reg = MetricsRegistry(path=str(tmp_path / "m.prom"))
        n_threads, per_thread = 4, 250
        errors = []
        stop = threading.Event()

        def writer(t):
            try:
                for i in range(per_thread):
                    reg.observe_histogram(
                        "dlrover_tpu_serving_ttft_seconds",
                        0.001 * (i % 40 + 1),
                        labels={"replica": str(t % 2)},
                    )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    text = reg.render_text()
                    assert (
                        "dlrover_tpu_serving_ttft_seconds" in text
                        or text == ""
                        or "_count" not in text
                    )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=writer, args=(t,))
            for t in range(n_threads)
        ] + [threading.Thread(target=reader)]
        for th in threads:
            th.start()
        for th in threads[:-1]:
            th.join(timeout=60)
        stop.set()
        threads[-1].join(timeout=60)
        assert not errors, errors
        series = reg.histogram_series(
            "dlrover_tpu_serving_ttft_seconds"
        )
        assert sum(h.count for h in series.values()) == (
            n_threads * per_thread
        )
        text = reg.render_text()
        assert 'replica="0"' in text and 'replica="1"' in text


class TestRetireSeries:
    def test_retire_drops_gauges_and_histograms(self, tmp_path):
        reg = MetricsRegistry(path=str(tmp_path / "m.prom"))
        for rep in ("0", "1"):
            reg.set_gauge(
                "dlrover_tpu_serving_tokens_per_s", 100.0,
                labels={"replica": rep},
            )
            reg.observe_histogram(
                "dlrover_tpu_serving_ttft_seconds", 0.05,
                labels={"replica": rep},
            )
        dropped = reg.retire_series({"replica": "1"})
        assert dropped >= 2
        text = reg.render_text()
        assert 'replica="1"' not in text
        assert 'replica="0"' in text
        assert reg.histogram(
            "dlrover_tpu_serving_ttft_seconds",
            labels={"replica": "1"},
        ) is None
        assert reg.histogram(
            "dlrover_tpu_serving_ttft_seconds",
            labels={"replica": "0"},
        ).count == 1

    def test_retire_unknown_labels_is_a_noop(self, tmp_path):
        reg = MetricsRegistry(path=str(tmp_path / "m.prom"))
        reg.set_gauge(
            "dlrover_tpu_serving_queue_depth", 3.0,
            labels={"replica": "0"},
        )
        assert reg.retire_series({"replica": "9"}) == 0
        assert 'replica="0"' in reg.render_text()


class TestRingSchemaVersioning:
    """Satellite 2: the shm payload carries its schema version, and a
    mixed-version dispatcher/replica pair is refused with a typed
    error naming BOTH versions — not misparsed."""

    def test_current_version_parses(self):
        from dlrover_tpu.rl.generation_service import (
            RING_SCHEMA_VERSION,
            _parse_stats,
        )

        stats = _parse_stats(
            [120.5, 3, 17, 0.66, 2, 0.25, 1.5, 0.08, 5, 9, 4, 2],
            RING_SCHEMA_VERSION,
        )
        assert stats["tokens_per_s"] == 120.5
        assert stats["queue_depth"] == 3
        assert stats["kv_utilization"] == 0.66
        assert stats["preemptions"] == 2
        assert stats["adoptions"] == 4
        assert stats["meta_rpcs"] == 2

    @pytest.mark.parametrize("bad_version", [3, 5])
    def test_mismatch_is_typed_and_names_both_versions(
        self, bad_version
    ):
        from dlrover_tpu.rl.generation_service import (
            RING_SCHEMA_VERSION,
            RingSchemaMismatch,
            _parse_stats,
        )

        with pytest.raises(RingSchemaMismatch) as exc:
            _parse_stats([0.0] * 8, bad_version)
        err = exc.value
        assert err.got == bad_version
        assert err.expected == RING_SCHEMA_VERSION
        assert f"v{bad_version}" in str(err)
        assert f"v{RING_SCHEMA_VERSION}" in str(err)
        assert isinstance(err, RuntimeError)


def _engine(**kw):
    kw.setdefault("interval_s", 0.05)
    kw.setdefault("sustain", 2)
    kw.setdefault("cooldown_s", 30.0)
    return ServingHealthEngine(**kw)


def _fleet(*rows):
    out = []
    for idx, outstanding in rows:
        out.append(
            {"idx": idx, "alive": True, "drained": False,
             "outstanding": outstanding}
        )
    return out


def _evaluate_rounds(eng, fleet, rounds):
    fired = []
    for _ in range(rounds):
        time.sleep(eng.interval_s + 0.01)
        fired.extend(eng.evaluate(fleet))
    return fired


class TestServingHealthEngine:
    def test_slo_straggler_needs_peers_and_sustain(self):
        eng = _engine(slo_ratio=2.0)
        for i in range(3):
            for _ in range(4):
                ttft = 1.0 if i == 2 else 0.1
                eng.note_result(i, ttft_s=ttft, tbt_p99_s=0.01,
                                e2e_s=ttft + 0.1)
        fleet = _fleet((0, 1), (1, 1), (2, 1))
        time.sleep(eng.interval_s + 0.01)
        first = eng.evaluate(fleet)
        assert first == []  # streak 1 < sustain 2
        snap = eng.snapshot()
        by_idx = {r["replica"]: r for r in snap["replicas"]}
        assert by_idx[2]["verdict"] == "ok"  # not yet sustained
        assert by_idx[2]["slo_score"] >= 2.0

        fired = _evaluate_rounds(eng, fleet, 1)
        assert [
            (v["replica"], v["reason"]) for v in fired
        ] == [(2, "slo_straggler")]
        assert fired[0]["value"] >= 2.0
        assert fired[0]["threshold"] == 2.0
        by_idx = {
            r["replica"]: r for r in eng.snapshot()["replicas"]
        }
        assert by_idx[2]["verdict"] == "slo_straggler"
        assert by_idx[2]["why"].startswith("slo_straggler")
        assert by_idx[0]["verdict"] == "ok"
        # cooldown: the breach persists but does not re-fire
        assert _evaluate_rounds(eng, fleet, 2) == []

    def test_straggler_needs_a_fleet(self):
        """A fleet of one has no peers to be slower than — no
        straggler verdict however slow it is."""
        eng = _engine(slo_ratio=2.0)
        for _ in range(6):
            eng.note_result(0, ttft_s=5.0, tbt_p99_s=1.0, e2e_s=9.0)
        fired = _evaluate_rounds(eng, _fleet((0, 1)), 3)
        assert fired == []
        (row,) = eng.snapshot()["replicas"]
        assert row["verdict"] == "ok"

    def test_dead_air_requires_outstanding_work(self):
        # dead_air_s must exceed one derivation interval, else the
        # recovery round below re-breaches before it can clear
        eng = _engine(dead_air_s=0.2)
        eng.note_result(0, ttft_s=0.1)
        eng.note_result(1, ttft_s=0.1)
        time.sleep(0.25)  # both silent past dead_air_s
        # replica 0 has work outstanding, replica 1 is idle
        fired = _evaluate_rounds(eng, _fleet((0, 2), (1, 0)), 2)
        assert [
            (v["replica"], v["reason"]) for v in fired
        ] == [(0, "dead_air")]
        by_idx = {
            r["replica"]: r for r in eng.snapshot()["replicas"]
        }
        assert by_idx[0]["verdict"] == "dead_air"
        assert by_idx[1]["verdict"] == "ok"
        # progress clears it: a completion refreshes the clock
        eng.note_result(0, ttft_s=0.1)
        _evaluate_rounds(eng, _fleet((0, 2), (1, 0)), 1)
        by_idx = {
            r["replica"]: r for r in eng.snapshot()["replicas"]
        }
        assert by_idx[0]["verdict"] == "ok"

    def test_kv_pressure_and_preempt_storm_from_stats(self):
        eng = _engine(kv_pressure=0.9, preempt_rate=3.0)
        fleet = _fleet((0, 1), (1, 1))
        cumulative = 0
        for round_no in range(2):
            cumulative += 4  # 4 NEW preemptions per interval
            eng.note_stats(
                0,
                {"tokens_per_s": 50.0, "kv_utilization": 0.97,
                 "preemptions": cumulative,
                 "prefix_hit_rate": 0.5},
            )
            eng.note_stats(
                1,
                {"tokens_per_s": 80.0, "kv_utilization": 0.4,
                 "preemptions": 0, "prefix_hit_rate": 0.5},
            )
            time.sleep(eng.interval_s + 0.01)
            fired = eng.evaluate(fleet)
        reasons = {(v["replica"], v["reason"]) for v in fired}
        assert reasons == {(0, "kv_pressure"), (0, "preempt_storm")}
        by_idx = {
            r["replica"]: r for r in eng.snapshot()["replicas"]
        }
        # priority: kv_pressure outranks preempt_storm
        assert by_idx[0]["verdict"] == "kv_pressure"
        assert by_idx[1]["verdict"] == "ok"
        assert by_idx[0]["kv_utilization"] == 0.97

    def test_dead_and_drained_replicas_are_named_not_scored(self):
        eng = _engine()
        eng.note_result(0, ttft_s=0.1)
        eng.note_result(1, ttft_s=0.1)
        fleet = [
            {"idx": 0, "alive": False, "drained": False,
             "outstanding": 0},
            {"idx": 1, "alive": True, "drained": True,
             "outstanding": 0},
        ]
        _evaluate_rounds(eng, fleet, 1)
        by_idx = {
            r["replica"]: r for r in eng.snapshot()["replicas"]
        }
        assert by_idx[0]["verdict"] == "dead"
        assert by_idx[1]["verdict"] == "drained"
        assert eng.snapshot()["fleet"]["replicas_alive"] == 0

    def test_instants_and_gauge_export(self, tmp_path):
        """A sustained breach writes one ``slo_breach`` + one
        ``serving_health`` instant (full label set) and exports the
        per-replica verdict gauge."""
        ev = tmp_path / "health.jsonl"
        set_default_event_logger(EventLogger(path=str(ev)))
        reg = MetricsRegistry(path=str(tmp_path / "m.prom"))
        set_default_registry(reg)
        try:
            eng = _engine(dead_air_s=0.05)
            eng.note_result(0, ttft_s=0.1)
            time.sleep(0.12)
            _evaluate_rounds(eng, _fleet((0, 1), (1, 0)), 2)
        finally:
            set_default_event_logger(None)
            set_default_registry(MetricsRegistry())
        names = _by_name(read_events(str(ev)))
        (breach,) = names["slo_breach"]
        labels = breach["labels"]
        assert labels["replica"] == 0
        assert labels["reason"] == "dead_air"
        assert labels["value"] >= labels["threshold"]
        verdicts = [
            e["labels"] for e in names["serving_health"]
            if e["labels"]["replica"] == 0
        ]
        assert any(
            v["verdict"] == "dead_air" and v["reason"] == "dead_air"
            for v in verdicts
        )
        text = reg.render_text()
        assert "dlrover_tpu_serving_health" in text
        assert 'replica="0"' in text

    def test_reset_forgets_derivation_history(self):
        eng = _engine(dead_air_s=0.05)
        eng.note_result(0, ttft_s=8.0)  # a compile-era outlier
        time.sleep(0.12)
        _evaluate_rounds(eng, _fleet((0, 1)), 2)
        assert eng.snapshot()["replicas"]
        eng.reset()
        snap = eng.snapshot()
        assert snap["replicas"] == []
        # and the breach may fire again immediately post-reset (the
        # cooldown ledger is part of the forgotten history)
        eng.note_result(0, ttft_s=0.1)
        time.sleep(0.12)
        fired = _evaluate_rounds(eng, _fleet((0, 1)), 2)
        assert [v["reason"] for v in fired] == ["dead_air"]

    def test_env_defaults_and_interval_floor(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_SERVING_SLO_RATIO", "3.5")
        monkeypatch.setenv("DLROVER_TPU_SERVING_DERIVE_S", "0.001")
        eng = ServingHealthEngine()
        assert eng.slo_ratio == 3.5
        assert eng.interval_s == 0.05  # floored: never spin
        assert eng.sustain >= 1


@pytest.fixture(scope="module")
def obs_engine(tmp_path_factory):
    """A 2-replica serving session with a private default registry
    (the dispatcher records into the process-wide default)."""
    os.environ["DLROVER_TPU_SOCKET_DIR"] = str(
        tmp_path_factory.mktemp("socks_obs")
    )
    reg = MetricsRegistry(
        path=str(tmp_path_factory.mktemp("reg") / "m.prom")
    )
    set_default_registry(reg)
    from dlrover_tpu.rl.generation_service import ServingEngine

    eng = ServingEngine(
        factory="dlrover_tpu.rl.generation_service:tiny_llama_factory",
        factory_kwargs=SERVE_CFG_KW,
        max_new_tokens=6,
        temperature=0.0,
        name=f"serve-obs-{os.getpid()}",
        num_replicas=2,
        max_slots=4,
        block_size=4,
        num_blocks=64,
        max_seq_len=48,
        prefill_chunk=8,
    )
    yield eng, reg
    eng.close()
    set_default_registry(MetricsRegistry())


@pytest.mark.heavy
class TestServingEngineObservatory:
    """One engine session: SLO surfaces while serving,
    then the kill-one-replica series-retirement regression."""

    def test_status_gains_slo_and_health(self, obs_engine):
        eng, reg = obs_engine
        rng = np.random.default_rng(5)
        ids = [
            eng.submit(
                rng.integers(0, 97, (4,)).astype(np.int32),
                max_new=6, seed=500 + i,
            )
            for i in range(6)
        ]
        for rid in ids:
            res = eng.result(rid, timeout=180.0)
            assert "error" not in res
        status = eng.status()
        assert PR14_STATUS_KEYS <= set(status)
        assert "slo" in status and "health" in status
        slo = status["slo"]
        assert set(slo) == {
            "ttft_p99_s", "tbt_p99_s", "e2e_p99_s",
            "queue_wait_p99_s", "fleet_prefix_hit_rate",
        }
        assert slo["ttft_p99_s"] > 0
        assert slo["e2e_p99_s"] >= slo["ttft_p99_s"]
        health = status["health"]
        assert {r["replica"] for r in health["replicas"]} >= {0, 1}
        for row in health["replicas"]:
            assert "why" in row and "verdict" in row
        text = reg.render_text()
        assert "dlrover_tpu_serving_ttft_seconds_bucket" in text
        assert 'replica="0"' in text and 'replica="1"' in text

    def test_killed_replica_series_are_retired(self, obs_engine):
        """Satellite 1: SIGKILL one replica — its per-replica gauge
        and histogram series disappear from the exposition instead of
        freezing at their last values, and the observatory names the
        death; the survivor keeps serving."""
        eng, reg = obs_engine
        eng.kill_replica(1)
        rng = np.random.default_rng(6)
        ids = [
            eng.submit(
                rng.integers(0, 97, (4,)).astype(np.int32),
                max_new=6, seed=600 + i,
            )
            for i in range(4)
        ]
        for rid in ids:
            res = eng.result(rid, timeout=180.0)
            assert "error" not in res
            assert res["replica"] == 0  # only the survivor serves
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if 'replica="1"' not in reg.render_text():
                break
            time.sleep(0.2)
        text = reg.render_text()
        assert 'replica="1"' not in text, (
            "dead replica's series still exposed:\n" + text
        )
        assert 'replica="0"' in text  # survivor still live
        deadline = time.monotonic() + 15.0
        verdict = None
        while time.monotonic() < deadline:
            health = eng.status().get("health") or {}
            by_idx = {
                r["replica"]: r
                for r in health.get("replicas", ())
            }
            verdict = by_idx.get(1, {}).get("verdict")
            if verdict == "dead":
                break
            time.sleep(0.2)
        assert verdict == "dead"


@pytest.mark.heavy
class TestEngineNamesInjectedFaults:
    def test_slowed_and_wedged_replicas_are_named_in_time(
        self, tmp_path_factory, monkeypatch
    ):
        """Four replicas, one sleeping 0.1 s in every iteration (slow
        but progressing) and one wedged after 24 tokens (alive,
        outstanding work, no progress), both through the engine's
        ``faults=`` seam: the ServingHealthEngine NAMES both with the
        right reason within three derivation intervals of the first
        breach it saw, and once the wedged replica is killed every
        request still completes exactly once on the survivors."""
        from dlrover_tpu.rl.generation_service import ServingEngine

        straggler, wedged = 2, 3
        for key, val in (
            ("DLROVER_TPU_SOCKET_DIR",
             str(tmp_path_factory.mktemp("skf"))),
            ("DLROVER_TPU_SERVING_DERIVE_S", "0.25"),
            ("DLROVER_TPU_SERVING_DEAD_AIR_S", "1.0"),
            ("DLROVER_TPU_SERVING_SUSTAIN", "2"),
            ("DLROVER_TPU_SERVING_SLO_RATIO", "2.0"),
            ("DLROVER_TPU_SERVING_COOLDOWN_S", "5"),
        ):
            monkeypatch.setenv(key, val)
        eng = ServingEngine(
            factory=(
                "dlrover_tpu.rl.generation_service:"
                "tiny_llama_factory"
            ),
            factory_kwargs=SERVE_CFG_KW,
            max_new_tokens=12,
            temperature=0.0,
            name=f"serve-faults-{os.getpid()}",
            num_replicas=4,
            max_slots=8,
            block_size=8,
            num_blocks=128,
            max_seq_len=64,
            prefill_chunk=8,
            # the sleep is on from the replica's start; the wedge
            # trips only past the warm-up's token budget
            faults={
                straggler: {"sleep_s": 0.1},
                wedged: {"wedge_after_tokens": 24},
            },
        )
        expect = {straggler: "slo_straggler", wedged: "dead_air"}
        first_streak, named = {}, {}
        rng = np.random.default_rng(17)
        prompts = [
            rng.integers(0, 97, (int(rng.integers(3, 21)),)).astype(
                np.int32
            )
            for _ in range(12)
        ]
        try:
            # warm-up: every replica's compile out of the SLO windows
            # (8 x 2 tokens stay under the wedge's budget wherever
            # they land), then start the derivations clean
            for rid in [
                eng.submit(p, max_new=2, seed=13000 + i)
                for i, p in enumerate(prompts[:8])
            ]:
                eng.result(rid, timeout=300.0)
            eng._health.reset()
            ids = [
                eng.submit(p, max_new=12, seed=1000 + i)
                for i, p in enumerate(prompts)
            ]
            deadline = time.monotonic() + 90.0
            while len(named) < 2 and time.monotonic() < deadline:
                health = eng.status()["health"]
                for row in health["replicas"]:
                    idx, reason = row["replica"], expect.get(
                        row["replica"]
                    )
                    if reason is None or idx in named:
                        continue
                    if reason in (row.get("streaks") or {}):
                        first_streak.setdefault(
                            idx, health["derivations"]
                        )
                    if row["verdict"] == reason:
                        named[idx] = (
                            row["why"],
                            health["derivations"]
                            - first_streak.get(
                                idx, health["derivations"]
                            ),
                        )
                time.sleep(0.05)
            assert set(named) == {straggler, wedged}, named
            for idx, (why, gap) in named.items():
                assert why.startswith(expect[idx]), (idx, why)
                assert gap <= 3, (idx, gap)
            eng.kill_replica(wedged)
            res = [eng.result(rid, timeout=300.0) for rid in ids]
            assert len(res) == len(ids)
            assert all(r["new_tokens"] == 12 for r in res)
            assert eng.status()["completed"] == len(ids) + 8
        finally:
            eng.close()
