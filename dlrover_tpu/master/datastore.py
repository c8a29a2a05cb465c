"""Durable Brain datastore: cross-restart job/fleet history (sqlite).

Reference parity: the Go Brain persists job metrics to MySQL so
optimization learns across restarts and across jobs
(``dlrover/go/brain/pkg/datastore/``, ``dbbase/recorder.go:280``,
``docs/design/db-design.md``).  The TPU redesign trades the external
DB for an embedded sqlite file: a single-master control plane needs
durability and queryability, not a fleet-shared SQL server — and a
file on the master's persistent volume survives master restarts, which
is the failure mode that matters (VERDICT-r3: "a master restart loses
everything learned").

Three recorders:
- strategy measurements  (workload signature -> (strategy, step time))
  — feeds the strategy service's CalibratedPlanner across restarts
- speed samples          (worker count -> records/sec per job)
  — feeds WorkerResource's marginal-gain decisions
- node events            (failures, OOMs, relaunches per job)
  — the diagnosis/audit trail

High-rate writes (speed samples, node events, timeline batches) are
WRITE-BEHIND: recorders enqueue rows on a bounded
in-memory queue and a single background flusher drains them with
per-table ``executemany`` + ONE commit per batch — a timeline burst
costs one fsync instead of one per event, and the report RPC path
never blocks on sqlite.  Readers drain the queue first, so
read-your-writes semantics are preserved exactly.  Strategy
measurements stay synchronous: they are one row per calibration step
and a concurrently-live neighbour master may read the shared file the
moment the recorder returns.  ``close()`` drains
everything and checkpoints the WAL (fsync'd durability).
One lock serializes the shared connection (sqlite's own locking is
per-process anyway).
"""

import json
import os
import sqlite3
import threading
import time
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger

_SCHEMA = """
CREATE TABLE IF NOT EXISTS strategy_measurements (
    workload TEXT NOT NULL,
    strategy TEXT NOT NULL,
    step_time_s REAL NOT NULL,
    created_at REAL NOT NULL,
    job TEXT NOT NULL DEFAULT ''
);
CREATE INDEX IF NOT EXISTS idx_meas_workload
    ON strategy_measurements (workload, created_at);
CREATE TABLE IF NOT EXISTS speed_samples (
    job TEXT NOT NULL,
    worker_count INTEGER NOT NULL,
    records_per_sec REAL NOT NULL,
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_speed_job
    ON speed_samples (job, worker_count, created_at);
CREATE TABLE IF NOT EXISTS node_events (
    job TEXT NOT NULL,
    node TEXT NOT NULL,
    event_type TEXT NOT NULL,
    detail TEXT NOT NULL DEFAULT '',
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_events_job
    ON node_events (job, created_at);
CREATE TABLE IF NOT EXISTS timeline_events (
    job TEXT NOT NULL,
    node INTEGER NOT NULL DEFAULT 0,
    rank INTEGER NOT NULL DEFAULT -1,
    inc INTEGER NOT NULL DEFAULT 0,
    name TEXT NOT NULL,
    ph TEXT NOT NULL,
    wall REAL NOT NULL,
    mono REAL NOT NULL DEFAULT 0,
    dur REAL,
    sid INTEGER,
    pid INTEGER,
    labels TEXT NOT NULL DEFAULT '{}',
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_timeline_job
    ON timeline_events (job, wall);
CREATE TABLE IF NOT EXISTS profiles (
    job TEXT NOT NULL,
    node INTEGER NOT NULL,
    kind TEXT NOT NULL DEFAULT 'capture',
    reason TEXT NOT NULL DEFAULT '',
    summary TEXT NOT NULL DEFAULT '{}',
    artifact TEXT NOT NULL DEFAULT '',
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_profiles_job
    ON profiles (job, created_at);
CREATE TABLE IF NOT EXISTS control_journal (
    job TEXT NOT NULL,
    seq INTEGER NOT NULL,
    component TEXT NOT NULL,
    op TEXT NOT NULL,
    args TEXT NOT NULL DEFAULT '{}',
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_journal_job
    ON control_journal (job, seq);
CREATE TABLE IF NOT EXISTS control_snapshots (
    job TEXT NOT NULL,
    seq INTEGER NOT NULL,
    state TEXT NOT NULL,
    created_at REAL NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_snapshot_job
    ON control_snapshots (job, seq);
CREATE TABLE IF NOT EXISTS control_meta (
    job TEXT PRIMARY KEY,
    job_epoch INTEGER NOT NULL DEFAULT 1,
    incarnation INTEGER NOT NULL DEFAULT 0,
    updated_at REAL NOT NULL
);
"""


def workload_signature(key: Tuple) -> str:
    """Stable string form of a workload-identity tuple (the strategy
    service's ``_workload_key``)."""
    return json.dumps(list(key), separators=(",", ":"))


_SQL_MEASUREMENT = (
    "INSERT INTO strategy_measurements "
    "(workload, strategy, step_time_s, created_at, job) "
    "VALUES (?,?,?,?,?)"
)
_SQL_SPEED = "INSERT INTO speed_samples VALUES (?,?,?,?)"
_SQL_NODE_EVENT = "INSERT INTO node_events VALUES (?,?,?,?,?)"
_SQL_TIMELINE = (
    "INSERT INTO timeline_events VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)"
)
_SQL_JOURNAL = "INSERT INTO control_journal VALUES (?,?,?,?,?,?)"
_SQL_PROFILE = "INSERT INTO profiles VALUES (?,?,?,?,?,?,?)"


class BrainDatastore:
    """Embedded durable store for the master's learned state."""

    #: write-behind linger: a burst accumulates this long before the
    #: flusher commits it as one batch
    FLUSH_AGE_S = 0.2
    #: bounded queue: recorders block (briefly) past this many pending
    #: rows instead of growing memory without bound
    MAX_PENDING = 10_000

    def __init__(self, db_path: str):
        self.path = db_path
        # write-behind state (all guarded by _wb_cond; _enqueued /
        # _flushed count ROWS so a drain barrier is a counter compare)
        self._wb_cond = threading.Condition()
        self._pending: List[Tuple[str, tuple]] = []
        self._enqueued = 0
        self._flushed = 0
        self._drain_waiters = 0
        self._closed = False
        #: per-job monotonic journal sequence, initialized lazily from
        #: MAX(seq) so a restarted master keeps appending after the
        #: rows its predecessor landed
        self._journal_seq: Dict[str, int] = {}
        self._journal_seq_lock = threading.Lock()
        # built here, started last: the startup prune below passes
        # through `_drain`, which names it
        self._flusher = threading.Thread(
            target=self._flusher_loop,
            name="brain-write-behind",
            daemon=True,
        )
        parent = os.path.dirname(os.path.abspath(db_path))
        os.makedirs(parent, exist_ok=True)
        self._lock = threading.Lock()
        # timeout + WAL: the store is no longer single-master — a
        # fleet can point several job masters at one db file (the
        # reference's cluster-wide Brain over MySQL,
        # ref: dlrover/go/brain/pkg/datastore/dbbase/recorder.go:280)
        # and WAL lets one master read while another commits
        self._conn = sqlite3.connect(
            db_path, check_same_thread=False, timeout=10.0
        )
        with self._lock:
            try:
                self._conn.execute("PRAGMA journal_mode=WAL")
                self._conn.execute("PRAGMA busy_timeout=10000")
            except sqlite3.OperationalError:
                pass  # read-only FS etc.: plain journaling still works
            self._conn.executescript(_SCHEMA)
            # migration: pre-r5 files lack the job column on
            # strategy_measurements (calibration provenance +
            # per-job pruning)
            try:
                self._conn.execute(
                    "ALTER TABLE strategy_measurements "
                    "ADD COLUMN job TEXT NOT NULL DEFAULT ''"
                )
            except sqlite3.OperationalError:
                pass  # column already present
            self._conn.commit()
        logger.info("brain datastore at %s", db_path)
        # startup hygiene: long-lived masters append forever, and the
        # reads are LIMITed but full-table scans (measured_workloads)
        # and the file itself keep growing — drop ancient rows here so
        # every restart bounds the store (ADVICE-r4).  The FIXED 30d
        # floor applies globally; the operator's env override applies
        # only to THIS job's rows when a job name is known — in a
        # shared multi-job db, one short-retention job restarting must
        # not delete its neighbours' history
        self.prune(30.0 * 24 * 3600)
        env_age = os.getenv("DLROVER_TPU_BRAIN_MAX_AGE_S")
        if env_age:
            try:
                age = float(env_age)
            except ValueError:
                logger.warning(
                    "ignoring malformed DLROVER_TPU_BRAIN_MAX_AGE_S"
                    "=%r", env_age,
                )
            else:
                own_job = os.getenv("DLROVER_TPU_JOB_NAME", "")
                if own_job:
                    self.prune(age, job=own_job)
                else:
                    # no job identity: a job=None prune would be
                    # GLOBAL and delete every other job's rows from a
                    # shared db (ADVICE-r5) — refuse, keep the fixed
                    # 30d floor above as the only global hygiene
                    logger.warning(
                        "DLROVER_TPU_BRAIN_MAX_AGE_S=%s set but "
                        "DLROVER_TPU_JOB_NAME is empty; skipping the "
                        "job-scoped startup prune (a global prune "
                        "would delete other jobs' history)",
                        env_age,
                    )
        self._flusher.start()

    # ----------------------------------------------- write-behind core
    def _submit(self, sql: str, rows: List[tuple]):
        """Record rows: enqueue for the background flusher."""
        if not rows:
            return
        with self._wb_cond:

            # bounded queue: backpressure instead of unbounded memory;
            # the flusher drains fast enough that this only trips on a
            # pathological burst
            while (
                len(self._pending) >= self.MAX_PENDING
                and not self._closed
                and self._flusher.is_alive()
            ):
                self._wb_cond.wait(0.05)
            if not self._closed and self._flusher.is_alive():
                self._pending.extend((sql, row) for row in rows)
                self._enqueued += len(rows)
                self._wb_cond.notify_all()
                return
        # post-close (or dead-flusher) writes fall back to
        # synchronous-direct so nothing silently vanishes
        with self._lock:
            self._conn.executemany(sql, rows)
            self._conn.commit()

    def _flusher_loop(self):
        while True:
            with self._wb_cond:
                while not self._pending and not self._closed:
                    self._wb_cond.wait()
                if not self._pending and self._closed:
                    return
                # linger so a burst coalesces into one commit — unless
                # we're closing or a reader is parked on a drain
                if not self._closed and not self._drain_waiters:
                    self._wb_cond.wait(self.FLUSH_AGE_S)
                batch, self._pending = self._pending, []
                self._wb_cond.notify_all()  # wake backpressure waiters
            self._write_batch(batch)
            with self._wb_cond:
                self._flushed += len(batch)
                self._wb_cond.notify_all()  # wake drain waiters

    def _write_batch(self, batch: List[Tuple[str, tuple]]):
        """Per-table ``executemany`` over consecutive same-SQL runs
        (insertion order preserved), ONE commit for the whole batch.
        Commit latency lands in the
        ``dlrover_tpu_datastore_flush_seconds`` histogram (self-obs)
        — its tail IS the durability lag of everything the journal
        claims committed."""
        # chaos hook: the enqueue->flush window is exactly where a
        # crash tears the write-behind tail; the fault plan can pin a
        # SIGKILL here to prove journal replay tolerates it
        from dlrover_tpu.common.fault_injection import maybe_crash
        from dlrover_tpu.observability.metrics import (
            record_datastore_flush,
        )

        maybe_crash("mid_report_flush")
        t0 = time.perf_counter()
        self._flush_batch_locked(batch)
        record_datastore_flush(
            len(batch), time.perf_counter() - t0
        )

    def _flush_batch_locked(self, batch: List[Tuple[str, tuple]]):
        with self._lock:
            try:
                i = 0
                while i < len(batch):
                    sql = batch[i][0]
                    j = i
                    while j < len(batch) and batch[j][0] == sql:
                        j += 1
                    self._conn.executemany(
                        sql, [row for _, row in batch[i:j]]
                    )
                    i = j
                self._conn.commit()
            except sqlite3.Error as e:
                logger.warning(
                    "write-behind flush dropped %d rows: %s",
                    len(batch), e,
                )
                try:
                    self._conn.rollback()
                except sqlite3.Error:
                    pass

    def health(self) -> dict:
        """The write-behind queue's live vitals for the master's
        self-telemetry: queue depth vs bound (backpressure distance)
        and the JOURNAL LAG — rows enqueued minus rows flushed, i.e.
        how much claimed-durable state a crash right now would lose.
        Cheap (one lock hold, no sqlite); safe to call per scrape."""
        with self._wb_cond:
            return {
                "queue_depth": len(self._pending),
                "queue_cap": self.MAX_PENDING,
                "enqueued_rows": self._enqueued,
                "flushed_rows": self._flushed,
                "lag_rows": max(self._enqueued - self._flushed, 0),
                "flusher_alive": self._flusher.is_alive(),
            }

    def _drain(self):
        """Barrier: block until every row enqueued so far is
        committed — readers call this first, preserving exact
        read-your-writes semantics over the async queue."""
        if threading.current_thread() is self._flusher:
            return  # the flusher itself must never self-deadlock
        with self._wb_cond:
            target = self._enqueued
            self._drain_waiters += 1
            self._wb_cond.notify_all()  # cut the flusher's linger short
            try:
                while self._flushed < target:
                    if not self._flusher.is_alive():
                        break  # dead flusher must not hang readers
                    self._wb_cond.wait(0.05)
            finally:
                self._drain_waiters -= 1

    # ------------------------------------------- strategy measurements
    def record_measurement(
        self,
        workload: str,
        strategy: Dict,
        step_time_s: float,
        job: str = "",
    ):
        """``job`` tags provenance: measurements are keyed by
        WORKLOAD (hardware+model signature), so any job's master can
        learn from any other job's calibration through a shared db
        file — the cluster-wide role of the reference's Brain.

        Deliberately SYNCHRONOUS beside the write-behind queue: a
        concurrently-live neighbour master reads this file directly,
        so a measurement must be committed (not parked in this
        process's queue) the moment the recorder returns — and the
        rate is one row per calibration step, not a hot path."""
        row = (
            workload,
            json.dumps(strategy, separators=(",", ":")),
            float(step_time_s),
            time.time(),
            job,
        )
        with self._lock:
            self._conn.execute(_SQL_MEASUREMENT, row)
            self._conn.commit()

    def load_measurements(
        self, workload: str, limit: int = 64
    ) -> List[Tuple[Dict, float]]:
        """Newest ``limit`` measurements for a workload, oldest
        first (matches the in-memory history ordering)."""
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT strategy, step_time_s FROM ("
                "  SELECT strategy, step_time_s, created_at"
                "  FROM strategy_measurements WHERE workload = ?"
                "  ORDER BY created_at DESC LIMIT ?"
                ") ORDER BY created_at ASC",
                (workload, limit),
            ).fetchall()
        out = []
        for strategy_json, step_time in rows:
            try:
                out.append((json.loads(strategy_json), step_time))
            except json.JSONDecodeError:
                continue
        return out

    def measured_workloads(self) -> List[str]:
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT DISTINCT workload FROM strategy_measurements"
            ).fetchall()
        return [r[0] for r in rows]

    # ------------------------------------------------- speed samples
    def record_speed(
        self, job: str, worker_count: int, records_per_sec: float
    ):
        self._submit(
            _SQL_SPEED,
            [
                (
                    job,
                    int(worker_count),
                    float(records_per_sec),
                    time.time(),
                )
            ],
        )

    def speed_history(
        self, job: str, max_age_s: Optional[float] = None
    ) -> Dict[int, float]:
        """Best observed speed per worker count (what WorkerResource's
        marginal-gain model consumes)."""
        q = (
            "SELECT worker_count, MAX(records_per_sec) "
            "FROM speed_samples WHERE job = ?"
        )
        args: List = [job]
        if max_age_s is not None:
            q += " AND created_at >= ?"
            args.append(time.time() - max_age_s)
        q += " GROUP BY worker_count"
        self._drain()
        with self._lock:
            rows = self._conn.execute(q, args).fetchall()
        return {int(n): float(v) for n, v in rows}

    # --------------------------------------------------- node events
    def record_node_event(
        self, job: str, node: str, event_type: str, detail: str = ""
    ):
        self._submit(
            _SQL_NODE_EVENT,
            [(job, str(node), event_type, detail, time.time())],
        )

    def node_events(
        self, job: str, limit: int = 100
    ) -> List[Dict]:
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT node, event_type, detail, created_at "
                "FROM node_events WHERE job = ? "
                "ORDER BY created_at DESC LIMIT ?",
                (job, limit),
            ).fetchall()
        return [
            {
                "node": n,
                "event_type": e,
                "detail": d,
                "created_at": t,
            }
            for n, e, d, t in rows
        ]

    # -------------------------------------------------- deep captures
    def record_profile(
        self,
        job: str,
        node: int,
        kind: str = "capture",
        reason: str = "",
        summary: Optional[Dict] = None,
        artifact: str = "",
    ):
        """One deep-capture (or profile) row: the diagnosis-triggered
        capture evidence survives master failover like the rest of
        the Brain."""
        self._submit(
            _SQL_PROFILE,
            [
                (
                    job,
                    int(node),
                    str(kind),
                    str(reason),
                    json.dumps(
                        summary or {},
                        separators=(",", ":"),
                        default=str,
                    ),
                    str(artifact),
                    time.time(),
                )
            ],
        )

    def profiles(self, job: str, limit: int = 32) -> List[Dict]:
        """Newest ``limit`` capture rows for a job, newest first."""
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT node, kind, reason, summary, artifact, "
                "created_at FROM profiles WHERE job = ? "
                "ORDER BY created_at DESC LIMIT ?",
                (job, limit),
            ).fetchall()
        out = []
        for node, kind, reason, summary, artifact, created_at in rows:
            try:
                parsed = json.loads(summary) if summary else {}
            except json.JSONDecodeError:
                parsed = {}
            out.append(
                {
                    "node": node,
                    "kind": kind,
                    "reason": reason,
                    "summary": parsed,
                    "artifact": artifact,
                    "created_at": created_at,
                }
            )
        return out

    # ---------------------------------------------- timeline events
    def record_timeline_events(self, job: str, events: List[Dict]):
        """Persist a batch of timeline records (the JSONL schema of
        ``observability/events.py``) — the master's merged job-event
        timeline survives master restarts like the rest of the Brain."""
        now = time.time()
        rows = []
        for e in events:
            if not isinstance(e, dict) or "name" not in e:
                continue
            rows.append(
                (
                    job,
                    int(e.get("node", 0) or 0),
                    int(e.get("rank", -1) if e.get("rank")
                        is not None else -1),
                    int(e.get("inc", 0) or 0),
                    str(e.get("name", "")),
                    str(e.get("ph", "i")),
                    float(e.get("wall", now) or now),
                    float(e.get("mono", 0.0) or 0.0),
                    float(e["dur"]) if e.get("dur") is not None
                    else None,
                    int(e["sid"]) if e.get("sid") is not None
                    else None,
                    int(e.get("pid", 0) or 0),
                    json.dumps(
                        e.get("labels") or {}, separators=(",", ":")
                    ),
                    now,
                )
            )
        # write-behind: a node's whole timeline batch is one enqueue;
        # the background flusher lands it (plus whatever else is
        # pending) with one executemany + one commit — the report RPC
        # path no longer pays sqlite latency
        self._submit(_SQL_TIMELINE, rows)

    def timeline_events(
        self, job: str, limit: int = 10000
    ) -> List[Dict]:
        """Newest ``limit`` timeline records, oldest first (ready for
        ``compute_ledger`` / ``export_chrome_trace``)."""
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT node, rank, inc, name, ph, wall, mono, dur, "
                "sid, pid, labels FROM ("
                "  SELECT * FROM timeline_events WHERE job = ?"
                "  ORDER BY wall DESC LIMIT ?"
                ") ORDER BY wall ASC",
                (job, limit),
            ).fetchall()
        out = []
        for (node, rank, inc, name, ph, wall, mono, dur, sid, pid,
             labels) in rows:
            rec = {
                "name": name,
                "ph": ph,
                "wall": wall,
                "mono": mono,
                "job": job,
                "node": node,
                "rank": rank,
                "inc": inc,
                "pid": pid,
            }
            if dur is not None:
                rec["dur"] = dur
            if sid is not None:
                rec["sid"] = sid
            try:
                parsed = json.loads(labels) if labels else {}
            except json.JSONDecodeError:
                parsed = {}
            if parsed:
                rec["labels"] = parsed
            out.append(rec)
        return out

    # --------------------------------------- control-plane durability
    def _next_journal_seq(self, job: str) -> int:
        with self._journal_seq_lock:
            if job not in self._journal_seq:
                with self._lock:
                    row = self._conn.execute(
                        "SELECT MAX(seq) FROM control_journal "
                        "WHERE job = ?",
                        (job,),
                    ).fetchone()
                self._journal_seq[job] = int(row[0] or 0)
            self._journal_seq[job] += 1
            return self._journal_seq[job]

    def journal_append(
        self, job: str, component: str, op: str, args: Dict
    ) -> int:
        """Append one control-plane mutation record (write-behind: the
        report RPC path that triggered it never blocks on sqlite).
        Returns the assigned sequence number."""
        seq = self._next_journal_seq(job)
        self._submit(
            _SQL_JOURNAL,
            [(
                job,
                seq,
                component,
                op,
                json.dumps(args, separators=(",", ":"), default=str),
                time.time(),
            )],
        )
        return seq

    def journal_seq(self, job: str) -> int:
        """Highest sequence number HANDED OUT so far (enqueued, not
        necessarily flushed) — the snapshot low-water mark."""
        with self._journal_seq_lock:
            if job in self._journal_seq:
                return self._journal_seq[job]
        self._drain()
        with self._lock:
            row = self._conn.execute(
                "SELECT MAX(seq) FROM control_journal WHERE job = ?",
                (job,),
            ).fetchone()
        return int(row[0] or 0)

    def journal_entries(
        self, job: str, since_seq: int = 0
    ) -> List[Tuple[int, str, str, Dict]]:
        """Journal records with ``seq > since_seq``, oldest first, as
        ``(seq, component, op, args)``.

        Torn-tail tolerance: a crash can leave the NEWEST record's
        ``args`` column unparseable; recovery truncates to the last
        complete record (everything after the first bad row is
        dropped with a warning) and NEVER raises — the dropped tail
        is at most the linger window of un-fsynced mutations, exactly
        what a crash loses anyway."""
        self._drain()
        with self._lock:
            rows = self._conn.execute(
                "SELECT seq, component, op, args FROM control_journal "
                "WHERE job = ? AND seq > ? ORDER BY seq ASC",
                (job, since_seq),
            ).fetchall()
        out: List[Tuple[int, str, str, Dict]] = []
        for seq, component, op, args in rows:
            try:
                parsed = json.loads(args) if args else {}
            except (json.JSONDecodeError, TypeError) as e:
                logger.warning(
                    "journal replay for %s truncated at seq %s "
                    "(torn tail: %s); %d records replayed",
                    job, seq, e, len(out),
                )
                break
            out.append((int(seq), component, op, parsed))
        return out

    def save_control_snapshot(self, job: str, state: Dict, seq: int):
        """Persist a compacted snapshot of the whole control-plane
        state at journal position ``seq`` and prune journal records it
        subsumes.  Synchronous (rare — one row per snapshot interval);
        replay = snapshot + entries with ``seq > snapshot.seq``."""
        payload = json.dumps(state, separators=(",", ":"), default=str)
        # flush pending write-behind journal rows first: a row with
        # seq <= snapshot.seq landing AFTER the prune would linger in
        # the table forever (harmless for replay — since_seq filters
        # it — but it defeats the compaction)
        self._drain()
        with self._lock:
            self._conn.execute(
                "DELETE FROM control_snapshots WHERE job = ?", (job,)
            )
            self._conn.execute(
                "INSERT INTO control_snapshots VALUES (?,?,?,?)",
                (job, int(seq), payload, time.time()),
            )
            self._conn.execute(
                "DELETE FROM control_journal "
                "WHERE job = ? AND seq <= ?",
                (job, int(seq)),
            )
            self._conn.commit()

    def load_control_snapshot(
        self, job: str
    ) -> Tuple[Optional[Dict], int]:
        """Newest snapshot for ``job`` as ``(state, seq)``; ``(None,
        0)`` when absent or unparseable (a torn snapshot falls back to
        journal-only replay)."""
        self._drain()
        with self._lock:
            row = self._conn.execute(
                "SELECT state, seq FROM control_snapshots "
                "WHERE job = ? ORDER BY seq DESC LIMIT 1",
                (job,),
            ).fetchone()
        if row is None:
            return None, 0
        try:
            return json.loads(row[0]), int(row[1])
        except (json.JSONDecodeError, TypeError) as e:
            logger.warning(
                "control snapshot for %s unreadable (%s); replaying "
                "journal from scratch", job, e,
            )
            return None, 0

    def bump_incarnation(self, job: str) -> Tuple[int, int]:
        """Register a master start: increments the incarnation, keeps
        the job epoch (a restarted master serves the SAME job).
        Returns ``(job_epoch, incarnation)``.  Synchronous — the pair
        fences every subsequent RPC, so it must be durable before the
        server opens."""
        with self._lock:
            now = time.time()
            self._conn.execute(
                "INSERT INTO control_meta VALUES (?, 1, 1, ?) "
                "ON CONFLICT(job) DO UPDATE SET "
                "incarnation = incarnation + 1, updated_at = ?",
                (job, now, now),
            )
            self._conn.commit()
            row = self._conn.execute(
                "SELECT job_epoch, incarnation FROM control_meta "
                "WHERE job = ?",
                (job,),
            ).fetchone()
        return int(row[0]), int(row[1])

    def bump_job_epoch(self, job: str) -> int:
        """Declare a NEW job generation on this master address: bumps
        the epoch so clients of the previous generation are fenced
        into a refresh, and drops the old generation's journal,
        snapshot and per-job epoch-scoped state."""
        # enqueued rows of the dying generation must not outlive it
        self._drain()
        with self._lock:
            now = time.time()
            self._conn.execute(
                "INSERT INTO control_meta VALUES (?, 1, 0, ?) "
                "ON CONFLICT(job) DO UPDATE SET "
                "job_epoch = job_epoch + 1, incarnation = 0, "
                "updated_at = ?",
                (job, now, now),
            )
            self._conn.execute(
                "DELETE FROM control_journal WHERE job = ?", (job,)
            )
            self._conn.execute(
                "DELETE FROM control_snapshots WHERE job = ?", (job,)
            )
            self._conn.commit()
            row = self._conn.execute(
                "SELECT job_epoch FROM control_meta WHERE job = ?",
                (job,),
            ).fetchone()
        with self._journal_seq_lock:
            self._journal_seq.pop(job, None)
        return int(row[0])

    def get_control_meta(self, job: str) -> Tuple[int, int]:
        """Current ``(job_epoch, incarnation)`` without bumping
        (``(1, 0)`` when the job was never registered)."""
        with self._lock:
            row = self._conn.execute(
                "SELECT job_epoch, incarnation FROM control_meta "
                "WHERE job = ?",
                (job,),
            ).fetchone()
        if row is None:
            return 1, 0
        return int(row[0]), int(row[1])

    def sweep_timeline(
        self,
        job: str,
        max_age_s: Optional[float] = None,
        max_rows: Optional[int] = None,
    ):
        """Retention sweep for ONE job's ``timeline_events`` rows:
        drop rows older than ``max_age_s`` AND cap the job to the
        newest ``max_rows`` (0 disables either bound).  Defaults come
        from ``DLROVER_TPU_TIMELINE_MAX_AGE_S`` /
        ``DLROVER_TPU_TIMELINE_MAX_ROWS`` (generous: 7 days / 500k
        rows).  Job-scoped on purpose — a shared multi-job Brain must
        never lose a neighbour's history to this job's sweep."""
        from dlrover_tpu.common.env import (
            timeline_max_age_s,
            timeline_max_rows,
        )

        if max_age_s is None:
            max_age_s = timeline_max_age_s()
        if max_rows is None:
            max_rows = timeline_max_rows()
        self._drain()
        with self._lock:
            if max_age_s and max_age_s > 0:
                self._conn.execute(
                    "DELETE FROM timeline_events "
                    "WHERE job = ? AND created_at < ?",
                    (job, time.time() - max_age_s),
                )
            if max_rows and max_rows > 0:
                # newest rows win: delete everything below the
                # max_rows-th newest (created_at, wall) position
                self._conn.execute(
                    "DELETE FROM timeline_events WHERE job = ? "
                    "AND rowid NOT IN ("
                    "  SELECT rowid FROM timeline_events "
                    "  WHERE job = ? "
                    "  ORDER BY created_at DESC, wall DESC LIMIT ?"
                    ")",
                    (job, job, int(max_rows)),
                )
            self._conn.commit()

    # ------------------------------------------------------- hygiene
    def prune(self, max_age_s: float, job: Optional[str] = None):
        """Drop rows older than ``max_age_s``; with ``job`` given,
        only that job's rows (a finished job's master cleans up after
        itself without touching its neighbours' history in a shared
        db)."""
        cutoff = time.time() - max_age_s
        self._drain()
        with self._lock:
            for table in (
                "strategy_measurements",
                "speed_samples",
                "node_events",
                "timeline_events",
                "profiles",
            ):
                q = f"DELETE FROM {table} WHERE created_at < ?"  # noqa: S608 - fixed table names
                args: List = [cutoff]
                if job is not None:
                    q += " AND job = ?"
                    args.append(job)
                self._conn.execute(q, args)
            self._conn.commit()

    def close(self):
        """Drain the write-behind queue (zero rows lost — pinned by
        tests), checkpoint the WAL so the bytes are fsync'd into the
        main db file, then close."""
        with self._wb_cond:
            self._closed = True
            self._wb_cond.notify_all()
        self._flusher.join(timeout=10.0)
        with self._lock:
            try:
                self._conn.commit()
                self._conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")
            except sqlite3.Error:
                pass  # non-WAL / read-only FS: commit already landed
            self._conn.close()


_default_store: Optional[BrainDatastore] = None


def get_default_datastore() -> Optional[BrainDatastore]:
    """Process-wide datastore, enabled by ``DLROVER_TPU_BRAIN_DB``
    (the master sets it; absent = history stays in-memory only)."""
    global _default_store
    if _default_store is None:
        path = os.getenv("DLROVER_TPU_BRAIN_DB", "")
        if path:
            _default_store = BrainDatastore(path)
    return _default_store
