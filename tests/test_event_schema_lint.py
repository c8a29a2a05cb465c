"""Tier-1 wrapper for ``scripts/check_event_schema.py``: the repo's
emit sites must all use the declared phase vocabulary + required
labels, and the lint must actually catch violations (a lint that
passes everything proves nothing)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "scripts", "check_event_schema.py")


def _run(*args):
    return subprocess.run(
        [sys.executable, LINT, *args],
        capture_output=True,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=120,
    )


def test_repo_emit_sites_conform():
    proc = _run()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "event_schema_violations=0" in proc.stdout


def test_lint_catches_violations(tmp_path):
    bad = tmp_path / "bad_emit.py"
    bad.write_text(
        "events = None\n"
        "def f(events, phase):\n"
        "    events.span('not_a_phase')\n"        # undeclared phase
        "    events.complete('step', 0.0, 1.0)\n"  # missing step label
        "    events.begin(phase)\n"                # non-literal phase
        "    events.instant('job_start')\n"        # fine
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert "not_a_phase" in proc.stdout
    assert "missing required label(s) ['step']" in proc.stdout
    assert "string literal" in proc.stdout


def test_lint_enforces_offload_copy_labels(tmp_path):
    """The host-offload DMA spans must carry bytes + throughput +
    the buffered flag — a site missing any of them fails the lint."""
    bad = tmp_path / "bad_offload.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('offload_copy', 0.0, 1.0,\n"
        "                    bytes=1, throughput_gbps=2.0)\n"
        "    events.complete('offload_copy', 0.0, 1.0, bytes=1,\n"
        "                    throughput_gbps=2.0, buffered=True)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert "missing required label(s) ['buffered']" in proc.stdout


def test_lint_enforces_fault_injected_labels(tmp_path):
    """Chaos markers must be attributable: ``fault_injected`` without
    kind+target is an anonymous blip in exactly the trace that needs
    precision."""
    bad = tmp_path / "bad_fault.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('fault_injected', kind='kill')\n"
        "    events.instant('fault_injected',\n"
        "                   kind='kill', target='master')\n"
        "    events.instant('master_restart')\n"
        "    events.instant('master_restart', incarnation=2)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert "missing required label(s) ['target']" in proc.stdout
    assert "missing required label(s) ['incarnation']" in proc.stdout


def test_lint_enforces_diagnosis_labels(tmp_path):
    """The observatory's conclusion markers must name the problem,
    the action and the node — an anonymous ``diagnosis`` instant is
    useless to the operator reading the trace."""
    bad = tmp_path / "bad_diagnosis.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('diagnosis', problem='hang')\n"
        "    events.instant('diagnosis', problem='hang',\n"
        "                   action='restart_process', node_rank=3)\n"
        "    events.instant('diagnosis', problem='straggler',\n"
        "                   action='none', node_rank=2,\n"
        "                   cause='x2.4 vs median')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert "missing required label(s) ['action', 'node_rank']" in (
        proc.stdout
    )


def test_lint_enforces_reshard_labels(tmp_path):
    """An elastic-reshard span without the world transition + moved
    bytes + throughput is uninterpretable — every label is REQUIRED,
    and a site missing any one of them fails the lint."""
    bad = tmp_path / "bad_reshard.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('reshard', 0.0, 1.0,\n"
        "                    from_world=8, to_world=4, bytes=1)\n"
        "    events.complete('reshard', 0.0, 1.0, to_world=4,\n"
        "                    bytes=1, throughput_gbps=2.0)\n"
        "    events.complete('reshard', 0.0, 1.0, from_world=8,\n"
        "                    to_world=4, bytes=1,\n"
        "                    throughput_gbps=2.0)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert "missing required label(s) ['throughput_gbps']" in (
        proc.stdout
    )
    assert "missing required label(s) ['from_world']" in proc.stdout


def test_lint_knows_reshard_and_drain_metrics():
    """The reshard gauges/counters and the ckpt drain/fallback
    counters are declared; a near-miss typo is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe2_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge('dlrover_tpu_reshard_gbps', 1.0)\n"
            "    reg.set_gauge('dlrover_tpu_reshard_bytes', 1.0)\n"
            "    reg.inc_counter('dlrover_tpu_reshard_total')\n"
            "    reg.inc_counter('dlrover_tpu_ckpt_drain_stuck')\n"
            "    reg.inc_counter("
            "'dlrover_tpu_ckpt_sigterm_fallback')\n"
            "    reg.inc_counter('dlrover_tpu_reshard_totals')\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_reshard_totals" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_catches_undeclared_metric_names():
    """A ``dlrover_tpu_``-prefixed gauge the package never declared
    (a typo'd dashboard series) must fail the lint; the observatory
    gauges themselves are declared.  The probe file must live INSIDE
    the package tree — metric policing is package-scoped."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge('dlrover_tpu_node_health', 1.0)\n"
            "    reg.set_gauge('dlrover_tpu_straggler_score', 1.0)\n"
            "    reg.set_gauge('dlrover_tpu_not_a_real_metric', 1)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_not_a_real_metric" in proc.stdout
        assert "dlrover_tpu_node_health" not in "".join(
            line
            for line in proc.stdout.splitlines()
            if "not a" in line and "declared" in line
        )
    finally:
        os.unlink(probe)


def test_lint_enforces_serving_span_labels(tmp_path):
    """Serving spans must carry their token accounting: a
    ``serve_step`` without tokens/new_tokens/throughput (or a
    prefill/decode leg without its count) is an unactionable blip in
    exactly the trace that explains a tokens/s dip."""
    bad = tmp_path / "bad_serving.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('serve_step', 0.0, 1.0, tokens=8,\n"
        "                    new_tokens=4)\n"
        "    events.complete('serve_step', 0.0, 1.0, tokens=8,\n"
        "                    new_tokens=4, throughput_tps=120.0)\n"
        "    events.complete('prefill', 0.0, 1.0)\n"
        "    events.complete('prefill', 0.0, 1.0, tokens=8)\n"
        "    events.complete('decode', 0.0, 1.0, new_tokens=4)\n"
        "    events.complete('decode', 0.0, 1.0)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['throughput_tps']" in proc.stdout
    )
    assert "missing required label(s) ['tokens']" in proc.stdout
    assert "missing required label(s) ['new_tokens']" in proc.stdout


def test_lint_enforces_preempt_verify_labels(tmp_path):
    """ISSUE-15 spans: a ``preempt`` without its cost/waste numbers
    or a ``verify`` without its drafted/accepted scoreboard is an
    unactionable blip — the lint must refuse both."""
    bad = tmp_path / "bad_preempt_verify.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('preempt', 0.0, 1.0, blocks_freed=3)\n"
        "    events.complete('preempt', 0.0, 1.0, blocks_freed=3,\n"
        "                    tokens_generated=7)\n"
        "    events.complete('verify', 0.0, 1.0, drafted=16)\n"
        "    events.complete('verify', 0.0, 1.0, drafted=16,\n"
        "                    accepted=12)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['tokens_generated']"
        in proc.stdout
    )
    assert "missing required label(s) ['accepted']" in proc.stdout


def test_lint_declares_incremental_serving_metrics():
    """The four ISSUE-15 gauges are declared vocabulary; an
    in-package near-miss typo is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_kv_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_kv_utilization', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_preemptions', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_prefix_hit_rate', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_accepted_tokens_per_step', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_kv_utilisation', 1.0)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_serving_kv_utilisation" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_declares_serving_metrics():
    """The four serving gauges are declared vocabulary; an in-package
    near-miss typo is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_serving_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_tokens_per_s', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_queue_depth', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_kv_blocks_used', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_p99_latency', 1.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_token_per_s', 1.0)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_serving_token_per_s" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_enforces_control_wait_retry_label(tmp_path):
    """A ``control_wait`` span opened as a retry pause must carry the
    attempt ordinal so retry storms are countable on the timeline."""
    bad = tmp_path / "bad_retry.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('control_wait', 0.0, 1.0, kind='retry')\n"
        "    events.complete('control_wait', 0.0, 1.0,\n"
        "                    kind='retry', retries=3)\n"
        "    events.span('control_wait', kind='reconnect')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert "missing the 'retries' label" in proc.stdout


def test_lint_enforces_scale_event_labels(tmp_path):
    """Brain planned-action markers must be auditable: a
    ``scale_decision`` / ``scale_execute`` without the rule that
    fired and the world transition it planned fails the lint."""
    bad = tmp_path / "bad_scale.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('scale_decision', action='grow')\n"
        "    events.instant('scale_decision', action='grow',\n"
        "                   reason='linear', from_world=2,\n"
        "                   to_world=3, plane='train')\n"
        "    events.instant('scale_execute', action='grow',\n"
        "                   reason='linear', from_world=2,\n"
        "                   plane='train')\n"
        "    events.instant('scale_execute', action='grow',\n"
        "                   reason='linear', from_world=2,\n"
        "                   to_world=3, plane='train',\n"
        "                   outcome='done')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) "
        "['reason', 'from_world', 'to_world', 'plane']"
        in proc.stdout
    )
    assert "missing required label(s) ['to_world']" in proc.stdout


def test_lint_enforces_scale_plane_label(tmp_path):
    """ISSUE-20: with the flywheel lending capacity across the
    train/serve boundary, an unlabeled scale instant cannot say WHICH
    plane moved — ``plane`` is required on both markers."""
    bad = tmp_path / "bad_plane.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('scale_decision', action='lend',\n"
        "                   reason='rollout_bound', from_world=4,\n"
        "                   to_world=3)\n"
        "    events.instant('scale_decision', action='lend',\n"
        "                   reason='rollout_bound', from_world=4,\n"
        "                   to_world=3, plane='serve')\n"
        "    events.instant('scale_execute', action='reclaim',\n"
        "                   reason='learner_bound', from_world=3,\n"
        "                   to_world=4, outcome='done')\n"
        "    events.instant('scale_execute', action='reclaim',\n"
        "                   reason='learner_bound', from_world=3,\n"
        "                   to_world=4, plane='serve',\n"
        "                   outcome='done')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert "missing required label(s) ['plane']" in proc.stdout


def test_lint_enforces_step_profile_labels(tmp_path):
    """A ``step_profile`` span without the category shares + achieved
    TFLOP/s + MFU is just a blip — every label is REQUIRED and a site
    missing any of them fails the lint."""
    bad = tmp_path / "bad_profile.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('step_profile', 0.0, 1.0, step=4,\n"
        "                    share_compute=0.5, tflops=10.0,\n"
        "                    mfu=0.3)\n"
        "    events.complete('step_profile', 0.0, 1.0, step=4,\n"
        "                    share_compute=0.5,\n"
        "                    share_collective=0.2, share_copy=0.1,\n"
        "                    share_infeed=0.1, share_idle=0.1,\n"
        "                    tflops=10.0, mfu=0.3)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['share_collective', "
        "'share_copy', 'share_infeed', 'share_idle']" in proc.stdout
    )


def test_lint_enforces_capture_instant_labels(tmp_path):
    """A ``capture`` instant must name the captured node and the
    reason — an anonymous capture marker is useless next to the
    diagnosis conclusion that triggered it."""
    bad = tmp_path / "bad_capture.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('capture', node_rank=3)\n"
        "    events.instant('capture', node_rank=3, reason='hang')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert "missing required label(s) ['reason']" in proc.stdout


def test_lint_declares_attribution_metrics():
    """The per-node MFU / device-share gauges are declared; an
    in-package near-miss typo is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_attr_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge('dlrover_tpu_node_mfu', 0.4)\n"
            "    reg.set_gauge('dlrover_tpu_device_share', 0.5)\n"
            "    reg.set_gauge('dlrover_tpu_device_shares', 0.5)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_device_shares" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_declares_autoscale_metrics():
    """The Brain's metric names are part of the declared vocabulary
    (dashboards key on them), and an in-package typo still fails."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_autoscale_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.inc_counter('dlrover_tpu_autoscale_decisions')\n"
            "    reg.inc_counter('dlrover_tpu_autoscale_executions')\n"
            "    reg.inc_counter('dlrover_tpu_autoscale_errors')\n"
            "    reg.set_gauge('dlrover_tpu_autoscale_world', 2)\n"
            "    reg.inc_counter('dlrover_tpu_autoscale_decsions')\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_autoscale_decsions" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_enforces_serve_request_lifecycle_labels(tmp_path):
    """ISSUE-16 spans: a ``serve_request`` must answer "was THIS
    request slow, and why" on its own — identity, placement, size,
    SLO numbers and the efficiency story are all REQUIRED; the
    children must at least carry the req_id that stitches the
    lifecycle together."""
    bad = tmp_path / "bad_serve_request.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('serve_request', 0.0, 1.0, req_id=4,\n"
        "                    replica='r0', prompt_tokens=7,\n"
        "                    gen_tokens=24, ttft_s=0.05,\n"
        "                    tbt_p99_s=0.004, route='affinity',\n"
        "                    slo_class='batch')\n"
        "    events.complete('serve_request', 0.0, 1.0, req_id=4,\n"
        "                    replica='r0', prompt_tokens=7,\n"
        "                    gen_tokens=24, ttft_s=0.05,\n"
        "                    tbt_p99_s=0.004, preempts=1,\n"
        "                    prefix_hit_blocks=2, route='local',\n"
        "                    slo_class='interactive')\n"
        "    events.complete('queue_wait', 0.0, 1.0)\n"
        "    events.complete('queue_wait', 0.0, 1.0, req_id=4)\n"
        "    events.complete('admit', 0.0, 1.0, req_id=4)\n"
        "    events.complete('resume', 0.0, 1.0, req_id=4)\n"
        "    events.complete('resume', 0.0, 1.0, req_id=4,\n"
        "                    resume_tokens=9)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['preempts', "
        "'prefix_hit_blocks']" in proc.stdout
    )
    assert "missing required label(s) ['req_id']" in proc.stdout
    assert (
        "missing required label(s) ['resume_tokens']" in proc.stdout
    )


def test_lint_enforces_fleet_routing_labels(tmp_path):
    """ISSUE-17 labels: a ``serve_request`` that does not say how it
    was routed or which SLO class it ran in cannot explain a fleet
    latency regression, and a ``kv_ship`` without its block/byte/
    throughput accounting is an invisible data-plane hop."""
    bad = tmp_path / "bad_fleet.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('serve_request', 0.0, 1.0, req_id=4,\n"
        "                    replica='r0', prompt_tokens=7,\n"
        "                    gen_tokens=24, ttft_s=0.05,\n"
        "                    tbt_p99_s=0.004, preempts=0,\n"
        "                    prefix_hit_blocks=2)\n"
        "    events.complete('serve_request', 0.0, 1.0, req_id=4,\n"
        "                    replica='r0', prompt_tokens=7,\n"
        "                    gen_tokens=24, ttft_s=0.05,\n"
        "                    tbt_p99_s=0.004, preempts=0,\n"
        "                    prefix_hit_blocks=2, route='ship',\n"
        "                    slo_class='batch')\n"
        "    events.complete('kv_ship', 0.0, 1.0, blocks=3,\n"
        "                    bytes=4096)\n"
        "    events.complete('kv_ship', 0.0, 1.0, blocks=3,\n"
        "                    bytes=4096, throughput_gbps=1.5)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['route', 'slo_class']"
        in proc.stdout
    )
    assert (
        "missing required label(s) ['throughput_gbps']"
        in proc.stdout
    )


def test_lint_no_longer_declares_the_kv_ship_counter():
    """The shipped-blocks counter went with its only emit site (PR 55:
    the replica's registry is exported by nobody; a ship's blocks are
    the ``kv_ship`` span's ``blocks`` label): an in-package site that
    brings the name back is refused until it is declared again, and the
    fleet's declared gauges still pass."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_ship_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge("
            "'dlrover_tpu_serving_prefix_hit_rate', 0.5)\n"
            "    reg.inc_counter("
            "'dlrover_tpu_serving_kv_shipped_blocks_total', 3)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert (
            "dlrover_tpu_serving_kv_shipped_blocks_total"
            in proc.stdout
        )
    finally:
        os.unlink(probe)


def test_lint_enforces_serving_health_instant_labels(tmp_path):
    """The observatory's verdict markers must name the replica and
    the reason — an anonymous ``serving_health`` / ``slo_breach``
    instant is exactly the "a replica is slow" blip the engine
    exists to replace."""
    bad = tmp_path / "bad_serving_health.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('serving_health', replica=2)\n"
        "    events.instant('serving_health', replica=2,\n"
        "                   verdict='dead_air', reason='dead_air')\n"
        "    events.instant('slo_breach', replica=2,\n"
        "                   reason='slo_straggler', value=4.2)\n"
        "    events.instant('slo_breach', replica=2,\n"
        "                   reason='slo_straggler', value=4.2,\n"
        "                   threshold=2.0)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['verdict', 'reason']"
        in proc.stdout
    )
    assert "missing required label(s) ['threshold']" in proc.stdout


def test_lint_declares_slo_histograms():
    """The four SLO histogram families and the serving-health verdict
    gauge are declared vocabulary; an in-package near-miss typo
    (``_secs``) is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_slo_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_serving_ttft_seconds', 0.1)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_serving_tbt_seconds', 0.01)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_serving_e2e_seconds', 1.0)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_serving_queue_wait_seconds', 0.01)\n"
            "    reg.set_gauge('dlrover_tpu_serving_health', 1.0)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_serving_ttft_secs', 0.1)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_serving_ttft_secs" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_enforces_kernel_autotune_labels(tmp_path):
    """A kernel_autotune span without the winner + sweep provenance
    (kernel/best_config/candidates/best_us) is unauditable — the
    lint must reject the bare span and accept the full one."""
    bad = tmp_path / "bad_autotune.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('kernel_autotune', 0.0, 1.0,\n"
        "                    kernel='decode', candidates=4)\n"
        "    events.complete('kernel_autotune', 0.0, 1.0,\n"
        "                    kernel='decode', best_config='{}',\n"
        "                    candidates=4, best_us=12.5)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['best_config', 'best_us']"
        in proc.stdout
    ), proc.stdout


def test_lint_declares_paged_kernel_metric():
    """The autotuner's best-time gauge is declared; a typo'd variant
    of it is not.  Package-scoped, so the probe lives in-tree."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_paged_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge('dlrover_tpu_paged_kernel_us', 42.0,\n"
            "                  labels={'kernel': 'decode',\n"
            "                          'backend': 'pallas'})\n"
            "    reg.set_gauge('dlrover_tpu_paged_kernel_usec', 42.0)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_paged_kernel_usec" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_enforces_flywheel_span_labels(tmp_path):
    """ISSUE-20 spans: a ``weight_publish`` without its
    generation/bytes/stall accounting cannot prove the zero-copy
    stall bound, a ``rollout_round`` without its scoreboard hides the
    staleness budget, and a ``trajectory`` without provenance is an
    unattributable sample — the lint refuses all three."""
    bad = tmp_path / "bad_flywheel.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.complete('weight_publish', 0.0, 1.0,\n"
        "                    generation=3, bytes=1024)\n"
        "    events.complete('weight_publish', 0.0, 1.0,\n"
        "                    generation=3, bytes=1024,\n"
        "                    stall_s=0.002)\n"
        "    events.complete('rollout_round', 0.0, 1.0, round=2,\n"
        "                    trajectories=16)\n"
        "    events.complete('rollout_round', 0.0, 1.0, round=2,\n"
        "                    trajectories=16, staleness_dropped=1)\n"
        "    events.complete('trajectory', 0.0, 0.0, req_id=7,\n"
        "                    generation=3)\n"
        "    events.complete('trajectory', 0.0, 0.0, req_id=7,\n"
        "                    generation=3, tokens=24)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert "missing required label(s) ['stall_s']" in proc.stdout
    assert (
        "missing required label(s) ['staleness_dropped']"
        in proc.stdout
    )
    assert "missing required label(s) ['tokens']" in proc.stdout


def test_lint_declares_flywheel_metrics():
    """The four flywheel gauges are declared vocabulary; an
    in-package near-miss typo is not.  Package-scoped, so the probe
    lives in-tree."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe_flywheel_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.set_gauge("
            "'dlrover_tpu_flywheel_generation', 3)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_flywheel_publish_stall_s', 0.002)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_flywheel_trajectories_per_s', 40.0)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_flywheel_staleness_dropped', 1)\n"
            "    reg.set_gauge("
            "'dlrover_tpu_flywheel_publish_stalls', 0.002)\n"
        )
    try:
        proc = _run(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, proc.stdout
        assert "dlrover_tpu_flywheel_publish_stalls" in proc.stdout
    finally:
        os.unlink(probe)


def test_lint_enforces_startup_stage_and_labels(tmp_path):
    """A ``startup`` span names its stage with a literal of
    ``STARTUP_STAGES`` (the set-up readers sum the stages by name) and
    carries no label outside the phase's closed set."""
    bad = tmp_path / "bad_startup.py"
    bad.write_text(
        "events = None\n"
        "def f(events, stage):\n"
        "    events.span('startup')\n"                   # no stage
        "    events.span('startup', stage='warmup')\n"    # undeclared
        "    events.begin('startup', stage=stage)\n"      # not a literal
        "    events.begin('startup', stage='pool', blocks=3)\n"
        "    events.complete('startup', 0.0, 1.0, stage='process')\n"
        "    events.begin('startup', stage='backend_init')\n"
        "    events.end('startup', 1, device_kind='cpu')\n"
        "    events.span('startup', stage='weights', bytes=1)\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=4" in proc.stdout, proc.stdout
    assert "missing required label(s) ['stage']" in proc.stdout
    assert proc.stdout.count("string literal of STARTUP_STAGES") == 2
    assert "undeclared label(s) ['blocks']" in proc.stdout


def test_lint_enforces_reply_and_compile_labels(tmp_path):
    """A ``reply`` span says whose reply it is, what rode beside it and
    what the loop's thread copied for it (``copied_bytes``, PR 62);
    a ``compile`` record's label set is closed (``program``, ``stage``,
    ``cache``), and a hand-made span around a whole compile may carry
    none of them."""
    bad = tmp_path / "bad_reply_compile.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.span('reply', req_id=1, copied_bytes=0)\n"
        "    events.complete('reply', 0.0, 1.0, req_id=1,\n"
        "                    per_token_bytes=0)\n"
        "    events.complete('reply', 0.0, 1.0, req_id=1,\n"
        "                    per_token_bytes=0, copied_bytes=0)\n"
        "    events.complete('compile', 0.0, 1.0, program='f',\n"
        "                    stage='trace', cached=True)\n"
        "    events.complete('compile', 0.0, 1.0, program='f',\n"
        "                    stage='backend_compile', cache='hit')\n"
        "    events.span('compile')\n"
    )
    proc = _run(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['per_token_bytes']" in proc.stdout
    )
    assert "missing required label(s) ['copied_bytes']" in proc.stdout
    assert "undeclared label(s) ['cached']" in proc.stdout
