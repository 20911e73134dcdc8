"""The bench tools keep working in tiny CPU configurations under tier-1
and honour their JSON contracts: one schema test a tool.
"""

import pytest


def test_ckpt_bench_tiny_cpu_schema(tmp_path):
    """The checkpoint bench must keep working in a tiny CPU config
    under tier-1 and honor its JSON contract (schema ckpt_bench/v1) —
    the guard that keeps the tool from bit-rotting."""
    import json

    from edl_tpu.tools import ckpt_bench

    out = ckpt_bench.run(tree_mb=2, workers=2,
                         directory=str(tmp_path), repeats=1)
    assert out["schema"] == "ckpt_bench/v1"
    assert out["roundtrip_ok"] is True
    assert out["tree_mb"] == pytest.approx(2.0, rel=0.1)
    assert out["sync"]["wall_ms"] > 0 and out["sync"]["mb_s"] > 0
    assert out["async"]["blocked_ms"] > 0
    assert out["async"]["persist_ms"] > 0 and out["async"]["mb_s"] > 0
    assert out["blocked_frac_of_sync"] > 0
    json.dumps(out)  # the whole report is JSON-serializable


def test_distill_bench_tiny_cpu_schema():
    """The distill data-plane bench must keep working in a tiny CPU
    config under tier-1 and honor its JSON contract (schema
    distill_bench/v1): both modes report throughput + occupancy, the
    two paths return byte-identical predictions, and the whole report
    serializes. No speedup assertion here — CI boxes are too noisy for
    a timing gate; the acceptance run does that offline."""
    import json

    from edl_tpu.tools import distill_bench

    out = distill_bench.run(model="linear", students=2, batches=6,
                            batch_size=4, feed_dim=16, fetch_dim=16,
                            max_batch=8, depth=3)
    assert out["schema"] == "distill_bench/v1"
    assert out["identical_ok"] is True
    for mode in ("serial", "pipelined"):
        assert out[mode]["wall_ms"] > 0
        assert out[mode]["predicts_s"] > 0
        assert out[mode]["goodput_mb_s"] > 0
        assert out[mode]["device_batches"] > 0
        assert 0 < out[mode]["occupancy_pct"] <= 100
    assert out["speedup_predicts_s"] > 0
    json.dumps(out)  # the whole report is JSON-serializable


def test_measure_resize_micro_peer_arc_cpu_schema(capsys):
    """Tier-1 smoke of the peer-restore bench arc: the hermetic micro
    mode (in-process save -> holdout peer -> placed restore) must run
    on CPU and emit a resize_bench/v1 record with the full per-stage
    downtime breakdown. No peer-vs-FS timing gate here — CI boxes are
    too noisy; the acceptance run compares the two arcs offline."""
    import json

    from edl_tpu.tools import measure_resize

    rc = measure_resize.main(["--arcs", "peer_restore_on", "--micro",
                              "--micro_mb", "2", "--platform", "cpu"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "error" not in out
    assert out["schema"] == "resize_bench/v1"
    assert out["metric"] == "resize_downtime_s_peer_restore_on"
    assert out["unit"] == "s" and out["mode"] == "micro"
    assert out["arc"] == "peer_restore_on"
    assert set(out["breakdown"]) == set(measure_resize.BREAKDOWN_STAGES)
    assert out["value"] >= out["breakdown"]["restore_s"] > 0
    assert out["restore"]["source"] == "peer"
    assert out["restore"]["peers"] >= 1
    assert out["restore"]["bytes"] > 0
    assert out["restore"]["version"] == 1
    json.dumps(out)  # round-trips


def test_measure_resize_kill_pod_arc_cpu_schema(capsys):
    """Tier-1 pin of the kill-one-pod arc (diskless fault tolerance,
    resize_bench/v1): the dead pod's state is rebuilt purely from
    partner-held erasure shards — ``fs_reads == 0`` across the whole
    parity window, byte-identical to the FS restore, surviving a
    partner SIGKILLed mid-rebuild — and the chaos-faulted rebuild
    drill degrades to the FS rung losslessly.

    Like its siblings it carries no gate on which window is faster:
    under six workers the parity restore read 0.23 s against the FS
    baseline's 0.088 s, which says nothing about the chip. Both times
    must be there and positive."""
    import json

    from edl_tpu.tools import measure_resize

    rc = measure_resize.main(["--arcs", "kill_pod", "--micro",
                              "--micro_mb", "16", "--platform", "cpu"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "error" not in out
    assert out["schema"] == "resize_bench/v1"
    assert out["arc"] == "kill_pod" and out["mode"] == "micro"
    assert set(out["breakdown"]) == set(measure_resize.BREAKDOWN_STAGES)
    assert out["shards"] == {"k": 2, "m": 1, "pushed": 3}

    # the diskless guarantee: zero FS reads, byte-identical, decoded
    # through a partner dying mid-rebuild
    restore = out["restore"]
    assert restore["source"] == "parity"
    assert restore["fs_reads"] == 0
    assert restore["byte_identical"] is True
    assert restore["killed_partner"] is True
    assert restore["owners"] == ["victim"]
    assert restore["bytes"] > 0
    assert restore["cold_restore_s"] > 0

    # sub-second, and the FS rung it replaces did read the file system
    assert out["fs_baseline"]["fs_reads"] > 0
    assert 0 < out["breakdown"]["restore_s"] < 1.0
    assert out["fs_baseline"]["restore_s"] > 0

    # the chaos drill: faulted rebuild -> FS rung, losslessly
    drill = out["fallback_drill"]
    assert drill["fault_fired"] is True
    assert drill["source"] == "fs"
    assert drill["fs_reads"] > 0
    assert drill["byte_identical"] is True
    json.dumps(out)  # round-trips


def test_measure_resize_live_arc_cpu_schema(capsys):
    """Tier-1 smoke of the live in-place resize arc: one worker process
    is resized 8→4→8 through the store 2PC without ever exiting, and
    the emitted resize_bench/v1 record must show the live shape —
    kill_s/barrier_s/restore_s structurally zero, reshard_s carrying
    the pause, the process alive at the end. No live-vs-stop_resume
    timing gate here — CI boxes are too noisy; the acceptance run
    compares the two arcs offline."""
    import json

    from edl_tpu.tools import measure_resize

    rc = measure_resize.main(["--arcs", "live", "--platform", "cpu",
                              "--from_devices", "8", "--timeout", "120"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "error" not in out and "warning" not in out
    assert out["schema"] == "resize_bench/v1"
    assert out["metric"] == "resize_downtime_s_live"
    assert out["arc"] == "live" and out["mode"] == "live"
    assert set(out["breakdown"]) == set(measure_resize.BREAKDOWN_STAGES)
    assert out["breakdown"]["kill_s"] == 0.0
    assert out["breakdown"]["barrier_s"] == 0.0
    assert out["breakdown"]["restore_s"] == 0.0
    assert out["value"] > 0 and out["breakdown"]["reshard_s"] > 0
    assert (out["from_devices"], out["to_devices"]) == (8, 4)
    assert out["process_survived"] is True
    assert out["grow"]["to_devices"] == 8  # same process grew back
    json.dumps(out)  # round-trips
    # time-ledger agreement: the worker's published ledger must
    # attribute the live pause. resize_pause owns the window except
    # the drain (nested ckpt_block) and any first-batch data_wait, so
    # resize_pause alone can't exceed the pause, and with the drain
    # added back it must cover it to within 10% (+50ms clock noise).
    ledger = out["ledger"]
    assert ledger is not None, "worker published no ledger totals"
    pause = out["value"]
    tol = 0.10 * pause + 0.05
    assert ledger["resize_pause"] <= pause + tol
    assert ledger["resize_pause"] + out["drain_s"] >= pause - tol, (
        ledger, out["value"], out["drain_s"])


def test_measure_resize_stop_resume_ledger_agreement(capsys):
    """Stop-resume arc + time-ledger agreement: the respawned trainer's
    restore + resize_pause must account for the in-process portion of
    the downtime (t_first_step - t_resume_start) to within 10%. The
    full bench value additionally counts kill/respawn wall time that
    belongs to no process — invisible to a per-process ledger by
    construction, which is why the record carries pause_in_process_s."""
    import json

    from edl_tpu.tools import measure_resize

    rc = measure_resize.main(["--arcs", "stop_resume", "--platform",
                              "cpu", "--from_devices", "8",
                              "--timeout", "120"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    out = json.loads(lines[0])
    assert "error" not in out
    assert out["schema"] == "resize_bench/v1"
    assert out["arc"] == "stop_resume"
    assert out["value"] >= out["pause_in_process_s"] > 0
    ledger = out["ledger"]
    assert ledger is not None, "worker published no ledger totals"
    pause = out["pause_in_process_s"]
    tol = 0.10 * pause + 0.05
    attributed = ledger["restore"] + ledger["resize_pause"]
    # the only other state that can own part of the window is the
    # first batch's data_wait; 10% bounds it
    assert attributed <= pause + tol, (ledger, out)
    assert attributed + ledger["data_wait"] >= pause - tol, (ledger,
                                                            out)


def test_store_bench_micro_schema():
    """The replicated-store bench must keep working hermetically under
    tier-1 and honor its JSON contract (schema store_bench/v1): the
    3-replica micro arc elects, quorum-acks writes, kills the leader,
    re-elects, and proves zero acknowledged-write loss; the fleet arc
    reports keepalive coalescing. No latency gate — CI boxes are too
    noisy; the acceptance run reads failover downtime offline."""
    import json

    from edl_tpu.tools import store_bench

    out = store_bench.run(writes=40, pods=16,
                          election_timeout=(0.15, 0.3))
    assert out["schema"] == "store_bench/v1"
    assert out["mode"] == "micro"
    rep = out["replication"]
    assert rep["replicas"] == 3
    assert rep["elect_ms"] > 0
    assert rep["writes_acked"] == 40
    assert rep["write_ops_s"] > 0
    assert rep["failover_downtime_ms"] > 0
    assert rep["lost_acked_writes"] == 0
    assert rep["linearizable_ok"] is True
    assert rep["leader_changed"] is True
    assert rep["commit_index"] >= 40
    fleet = out["fleet"]
    assert fleet["pods"] == 16
    assert fleet["refreshes_ok"] == 16
    assert fleet["per_lease_ok"] == 16
    assert fleet["coalesced_ms"] > 0 and fleet["per_lease_ms"] > 0
    assert fleet["coalesce_speedup"] > 0
    json.dumps(out)  # the whole report is JSON-serializable


def test_store_bench_fleet_watch_schema():
    """The fleet-watch arc must keep working hermetically under tier-1
    and honor its store_bench/v1 contract at the acceptance fleet size:
    both paths report propagation p50/p99 and store_rpcs_per_event, the
    relay tree beats the direct fan-out by the O(log N) margin (>=8x
    for RPCs per event AND store writes per obs tick at 2048 pods), the
    relay-kill drill loses zero events and reattaches its watchers. No
    latency gate — CI boxes are too noisy; the acceptance run reads
    propagation p99 offline."""
    import json

    from edl_tpu.tools import store_bench

    out = store_bench.run(pods=2048, watchers=8, watch_events=6,
                          arcs=("fleet_watch",))
    assert out["schema"] == "store_bench/v1"
    assert out["mode"] == "micro"
    fw = out["fleet_watch"]
    assert fw["pods"] == 2048
    assert fw["depth"] >= 2          # the drill needs a mid relay
    assert fw["interior_relays"] >= 1
    for path in ("direct", "relay"):
        assert fw[path]["publish_p50_ms"] is not None
        assert fw[path]["publish_p99_ms"] is not None
        assert fw[path]["publish_p99_ms"] >= fw[path]["publish_p50_ms"]
        assert fw[path]["lost_events"] == 0
        assert fw[path]["store_rpcs_per_event"] > 0
    # the O(log N) claim: one upstream pump per tree vs one poll loop
    # per pod, and one folded obs write vs N flat writes
    assert fw["relay"]["store_rpcs_per_event"] \
        < fw["direct"]["store_rpcs_per_event"]
    assert fw["rpc_reduction_x"] >= 8
    assert fw["obs_reduction_x"] >= 8
    # the relay-kill drill: lossless by since_rev resume, and the
    # orphaned watchers re-adopted a live ancestor
    assert fw["relay"]["kill_events"] > 0
    assert fw["relay"]["reattached_watchers"] >= 1
    json.dumps(out)  # the whole report is JSON-serializable


def test_data_bench_micro_schema():
    """The elastic data-plane bench must keep working in a tiny CPU
    config under tier-1 and honor its JSON contract (schema
    databench/v1): both arcs report throughput / latency / steal /
    idle, the two arcs move byte-identical record streams, and the
    whole report serializes. No speedup assertion here — CI boxes are
    too noisy for a timing gate; the acceptance run does that offline."""
    import json

    from edl_tpu.tools import data_bench

    out = data_bench.run(files=2, rows=96, dim=32, batch_size=16,
                         step_ms=1.0, fetch_ahead=4)
    assert out["schema"] == "databench/v1"
    assert out["identical_ok"] is True
    for arc in ("serial_row", "pipelined_col"):
        assert out[arc]["wall_ms"] > 0
        assert out[arc]["batches"] == 12          # 2 files * 96/16
        assert out[arc]["records"] == 192
        assert out[arc]["records_s"] > 0
        assert out[arc]["fetch_ms_p50"] >= 0
        assert out[arc]["fetch_ms_p99"] >= out[arc]["fetch_ms_p50"]
        assert out[arc]["steal_ratio"] == 1.0     # pure consumer arc
        assert 0 <= out[arc]["consumer_idle_pct"] <= 100
        assert out[arc]["lost"] == 0
        assert out[arc]["pool_dials"] >= 1
    assert out["speedup_records_s"] > 0
    json.dumps(out)  # the whole report is JSON-serializable


def test_obs_bench_micro_schema():
    """The observability-overhead bench must keep working in a tiny CPU
    config under tier-1 and honor its JSON contract (schema
    obs_bench/v1): both arcs run the pipelined data hot loop, the
    primitive microbenchmarks cover every handle op enabled AND
    disabled, and the registry is left enabled afterwards. No overhead
    gate here — CI boxes are too noisy for a timing assertion; the <2%
    acceptance number is measured offline."""
    import json

    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.tools import obs_bench

    out = obs_bench.run(mode="micro", files=2, rows=64, dim=32,
                        batch_size=16, step_ms=0.2)
    assert out["schema"] == "obs_bench/v1"
    for arc in ("on", "off"):
        assert out[arc]["records_s"] > 0
        assert out[arc]["lost"] == 0
    assert out["overhead_pct"] is not None
    prim = out["primitives"]
    for state in ("enabled", "disabled"):
        for op in ("counter_inc_ns", "labeled_inc_ns", "gauge_set_ns",
                   "histogram_observe_ns", "span_noop_ns"):
            assert prim[state][op] > 0
    assert obs_metrics.enabled()  # the bench must restore the switch
    det = out["detectors"]
    assert det["pods"] >= 2 and det["windows"] > 0
    assert det["tick_ms_p50"] > 0
    assert det["tick_ms_max"] >= det["tick_ms_p50"]
    strag = det["straggler"]
    assert strag["clean_false_positives"] == 0
    assert strag["detected_window"] is not None
    # the detection-latency acceptance bound: the injected straggler is
    # flagged within 2 publish windows (virtual clock — not host-noisy)
    assert strag["detection_windows"] <= 2
    json.dumps(out)  # the whole report is JSON-serializable


def test_health_report_schema():
    """health_report/v1 contract: every field the doctor and job_stats
    consume, produced by a real HealthMonitor.evaluate() pass over the
    detector bench's synthetic fleet."""
    import json

    from edl_tpu.obs import events as obs_events
    from edl_tpu.obs import health as obs_health
    from edl_tpu.tools import obs_bench

    monitor = obs_health.HealthMonitor(
        coord=None, pod_id="guard-monitor", interval=10.0,
        events=obs_events.EventLog(), clock=lambda: 1_000_000.0)
    state = {}
    steps = {"pod-%02d" % p: (600.0 if p == 3 else 100.0)
             for p in range(4)}
    report = None
    for w in range(4):
        docs = obs_bench._synth_fleet_docs(4, w, steps, state,
                                           1_000_000.0, 10.0)
        report = monitor.evaluate(docs, now=1_000_000.0 + w * 10.0)
    assert report["schema"] == "health_report/v1"
    assert report["fleet"]["verdict"] == "critical"
    assert report["fleet"]["pods_total"] == 4
    assert report["fleet"]["pods_degraded"] == ["pod-03"]
    assert set(report["pods"]) == set(steps)
    assert report["pods"]["pod-03"]["verdict"] == "critical"
    f = report["findings"][0]
    for field in ("detector", "pod", "severity", "summary", "metric",
                  "value", "baseline", "threshold"):
        assert field in f
    assert isinstance(report["slos"], list)
    assert report["preferred_victims"] == ["pod-03"]
    kinds = [e["kind"] for e in report["events"]]
    assert "health.degraded" in kinds
    json.dumps(report)


def test_doctor_report_schema():
    """doctor_report/v1 contract, including the no-monitor degradation:
    verdict "unknown" with an explanatory summary when no health report
    has ever been published."""
    import json

    from edl_tpu.tools import job_doctor

    doc = job_doctor.diagnose({"job_id": "j", "job_status": None,
                               "health": None, "obs": {}})
    assert doc["schema"] == "doctor_report/v1"
    assert doc["verdict"] == "unknown"
    assert doc["findings"] == []
    assert doc["summary"]
    json.dumps(doc)
    job_doctor.render(doc)  # the human surface renders without a report


def test_obs_bench_ledger_section_schema():
    """obs_bench "ledger" section contract: both arcs timed, per-step
    overhead derived, and the acceptance criterion (<1%) carried in
    the record. No overhead gate here — CI boxes are too noisy; the
    <1% number is measured offline like every other bench figure."""
    import json

    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.tools import obs_bench

    out = obs_bench.bench_ledger(iters=200, work_us=50.0, repeats=2)
    assert out["iters"] == 200 and out["repeats"] == 2
    assert out["enabled_s"] > 0 and out["disabled_s"] > 0
    assert out["overhead_pct"] is not None
    assert out["criterion_pct"] == 1.0
    assert obs_metrics.enabled()  # the bench must restore the switch
    json.dumps(out)


def test_goodput_doc_schema():
    """goodput/v1 contract: every field job_stats --pretty and the
    doctor read, produced by a real GoodputMerger fold."""
    import json

    from edl_tpu.obs import ledger as obs_ledger

    m = obs_ledger.GoodputMerger()
    m.update("pod-00", {"compute": 80.0, "ckpt_block": 15.0,
                        "idle": 5.0})
    m.update("pod-01", {"compute": 95.0, "data_wait": 5.0})
    doc = m.doc(now=1_000_000.0)
    assert doc["schema"] == "goodput/v1"
    fleet = doc["fleet"]
    for field in ("total_s", "goodput_s", "goodput_pct", "badput"):
        assert field in fleet
    assert fleet["badput"] == sorted(fleet["badput"],
                                     key=lambda b: -b["seconds"])
    for b in fleet["badput"]:
        assert set(b) == {"state", "seconds", "share_pct"}
    for pod, cell in doc["pods"].items():
        for field in ("total_s", "goodput_s", "goodput_pct",
                      "top_badput", "states"):
            assert field in cell
    assert set(doc["spread"]) == {"goodput_pct_min", "goodput_pct_max",
                                  "states"}
    json.dumps(doc)


def test_blackbox_doc_schema(tmp_path):
    """blackbox/v1 contract: every field --postmortem renders, produced
    by a real FlightRecorder dump; bounded and JSON-round-trippable."""
    import json

    from edl_tpu.obs import flight as obs_flight

    rec = obs_flight.FlightRecorder("guard-pod", out_dir=str(tmp_path))
    path = rec.dump("trainer_exit", RuntimeError("guard"))
    with open(path) as f:
        box = json.load(f)
    assert box["schema"] == "blackbox/v1"
    for field in ("ts", "pod", "pid", "reason", "exception", "events",
                  "spans", "metrics", "ledger", "threads", "context"):
        assert field in box
    assert len(box["events"]) <= obs_flight.MAX_EVENTS
    assert len(box["spans"]) <= obs_flight.MAX_SPANS
    assert len(box["threads"]) <= obs_flight.MAX_THREAD_DUMP
    json.dumps(box)


def test_obs_bench_autopilot_arc_schema():
    """obs_bench "autopilot" section contract: the policy-engine arc
    rides every monitor tick over the synthetic fleet, the seeded
    straggler draws an evict within 2 windows of detection, the clean
    half of the run produces ZERO actions, and the combined
    evaluate+on_report tick cost is carried for the <2%-of-interval
    criterion (measured offline — no timing gate here)."""
    import json

    from edl_tpu.tools import obs_bench

    out = obs_bench.bench_autopilot(pods=6, windows=12)
    assert out["pods"] == 6 and out["windows"] == 12
    assert out["interval_s"] > 0
    assert out["tick_ms_p50"] > 0
    assert out["tick_ms_max"] >= out["tick_ms_p50"]
    assert out["overhead_pct_of_interval"] >= 0
    strag = out["straggler"]
    for field in ("victim", "injected_window", "detected_window",
                  "action_window", "action_latency_windows"):
        assert field in strag
    assert strag["detected_window"] is not None
    assert strag["action_window"] is not None
    # the acceptance bound: the evict lands within 2 windows of the
    # detection verdict (virtual clock — not host-noisy)
    assert strag["action_latency_windows"] <= 2
    assert out["clean_actions"] == 0   # quiet fleet -> quiet engine
    assert out["actions_total"] >= 1   # the straggler WAS acted on
    json.dumps(out)


def test_action_record_schema():
    """action/v1 contract: every field job_stats/job_doctor render and
    load_actions filters on, produced by a real Autopilot apply pass
    and round-tripped through the store journal."""
    import json

    from edl_tpu.obs import autopilot as obs_autopilot

    class _Store(object):
        def __init__(self):
            self.store = {}

        def set_server_permanent(self, service, server, value):
            self.store[(service, server)] = value

        def get_value(self, service, server):
            return self.store.get((service, server))

        def get_service(self, service):
            return [(srv, v) for (svc, srv), v in self.store.items()
                    if svc == service]

    coord = _Store()
    ap = obs_autopilot.Autopilot(coord, "guard-monitor", mode="on",
                                 evict_fn=lambda pod: True,
                                 clock=lambda: 1_000_000.0)
    report = {"schema": "health_report/v1", "ts": 1_000_000.0,
              "fleet": {"verdict": "critical", "pods_total": 3,
                        "pods_degraded": ["pod-x"]},
              "findings": [{"detector": "straggler", "pod": "pod-x",
                            "severity": "critical", "summary": "slow",
                            "event_ids": [7]}],
              "preferred_victims": ["pod-x"], "goodput": {},
              "events": []}
    ap.on_report(report)
    actions = ap.on_report(report)
    assert len(actions) == 1
    a = actions[0]
    assert a["schema"] == "action/v1"
    for field in ("id", "seq", "ts", "kind", "mode", "actor", "target",
                  "reason", "cause", "outcome", "attempts", "error",
                  "result"):
        assert field in a
    assert a["kind"] in obs_autopilot.ACTION_KINDS
    assert a["mode"] in ("applied", "dry_run")
    assert a["outcome"] in ("applied", "dry_run", "failed")
    cause = a["cause"]
    for field in ("report_ts", "detector", "summary", "evidence_ids"):
        assert field in cause
    assert cause["evidence_ids"] == [7]
    # the stored journal round-trips and filters on the schema tag
    assert [x["id"] for x in obs_autopilot.load_actions(coord)] \
        == [a["id"]]
    json.dumps(a)


def test_serve_bench_micro_schema():
    """Tier-1 pin of the serving-plane bench contract (schema
    serve_bench/v1): the micro mode must force a full
    scale-out -> overload -> shed -> scale-in cycle under seeded chaos
    and prove the serving-plane guarantees — saturation sheds are
    typed OverloadedErrors with retry-after hints (never a timeout
    pile-up), the drain-safe decommission strands zero requests, the
    scaler's dry replay journals the identical action stream, and a
    clean low-load fleet produces zero scaler actions and zero sheds.
    The shed-rate and zero-stranded fields are MANDATORY: a report
    without them is a schema break, not a passing run."""
    import json

    from edl_tpu.tools import serve_bench

    out = serve_bench.run(mode="micro", seed=7)
    assert out["schema"] == "serve_bench/v1"
    assert out["sent"] > 0 and out["ok"] > 0
    assert out["goodput_rps"] > 0

    # overload produced typed sheds, and ONLY typed sheds: no timeout
    # pile-up, no untyped errors at saturation
    assert out["shed"]["total"] > 0
    assert out["shed"]["rate"] > 0
    assert out["shed"]["with_retry_after_hint"] > 0
    assert sum(out["shed"]["by_reason"].values()) == out["shed"]["total"]
    assert out["timeouts"] == 0
    assert out["untyped_errors"] == 0

    # zero stranded requests, by count AND by drain report
    assert out["stranded"] == 0
    assert out["drain"]["zero_stranded"] is True
    assert all(r["drained"] and r["pending_rows"] == 0
               for r in out["drain"]["reports"])

    # the forced cycle really scaled out and back in, and the drain
    # chaos drill fired on the real drain path
    assert out["scaler"]["scale_out"] >= 1
    assert out["scaler"]["scale_in"] >= 1
    assert out["faults_fired"].get("serve.drain", 0) >= 1

    # dry mode journals the IDENTICAL action stream to on mode
    assert out["dry_parity_ok"] is True
    assert out["live_action_stream"] == out["dry_action_stream"]

    # stats RPCs stayed answerable under overload (strict priority)
    assert out["stats_rpc_ms"]["p99"] is not None
    assert out["stats_rpc_ms"]["p99"] < out["latency_ms"]["p99"]

    # a clean fleet at low load: zero sheds, zero scaler actions
    assert out["clean"]["shed_total"] == 0
    assert out["clean"]["scaler_actions"] == 0
    assert out["clean"]["stranded"] == 0

    json.dumps(out)  # the whole report is JSON-serializable


def test_reshard_bench_cpu_schema(capsys):
    """Tier-1 pin of the cross-mesh reshard bench contract (schema
    reshard_bench/v1): every arc must be byte-identical to stop-resume
    and carry a sharding record, and the headline dp->dp x tp arc must
    move strictly fewer bytes than a wholesale restore of the state —
    but not zero (the dp-sharded moment really re-rows). No live-vs-
    stop_resume timing gate — CI boxes are too noisy; the acceptance
    run compares the pause columns offline."""
    import json

    from edl_tpu.tools import reshard_bench

    rc = reshard_bench.main([])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == len(reshard_bench.ARCS)
    by_arc = {}
    for line in lines:
        out = json.loads(line)
        assert "error" not in out, out
        assert out["schema"] == "reshard_bench/v1"
        assert out["byte_identical"] is True
        assert out["saved_record"] is True
        assert out["live_pause_s"] > 0
        assert out["stop_resume_s"] > 0
        assert 0 <= out["bytes_moved"] <= out["bytes_needed"]
        assert out["state_bytes"] > 0
        by_arc[out["arc"]] = out
    assert set(by_arc) == {"dp_to_dp_tp", "tp_change", "pp_resplit"}
    # the headline acceptance gate
    arc = by_arc["dp_to_dp_tp"]
    assert arc["from_mesh"] == {"dp": 4}
    assert arc["to_mesh"] == {"dp": 2, "tp": 2}
    assert 0 < arc["bytes_moved"] < arc["state_bytes"]
    # every arc keeps some state in place — the overlap fast path is
    # doing work (moved strictly under the wholesale volume)
    for out in by_arc.values():
        assert out["bytes_moved"] < out["bytes_needed"]


def test_measure_resize_live_sharded_arc_mesh_records(capsys):
    """The sharded live arc: a dp x tp worker (--mesh dp,tp) is resized
    4->2->4 through the 2PC with the tp axis pinned on the intent; the
    worker must keep tp=2 across both transitions, survive in place,
    and publish its mesh shape in every resize_timing record (the
    from_mesh/mesh pair in the emitted bench record)."""
    import json

    from edl_tpu.tools import measure_resize

    rc = measure_resize.main(["--arcs", "live", "--platform", "cpu",
                              "--from_devices", "4", "--mesh", "dp,tp",
                              "--timeout", "120"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "error" not in out and "warning" not in out, out
    assert out["schema"] == "resize_bench/v1"
    assert out["mode"] == "live"
    assert out["process_survived"] is True
    # the worker started on dp=2 x tp=2 and shrank to dp=1 x tp=2:
    # tp rides the intent, dp absorbs the world change
    assert out["from_mesh"]["dp"] == 2 and out["from_mesh"]["tp"] == 2
    assert out["mesh"]["dp"] == 1 and out["mesh"]["tp"] == 2
    # ...and grew back to the full factorization in the same process
    assert out["grow"]["mesh"]["dp"] == 2
    assert out["grow"]["mesh"]["tp"] == 2
    json.dumps(out)  # round-trips


# -- roofline_gap/v1 (measured-vs-predicted roofline bench) ---------------


def test_roofline_gap_micro_cpu_schema(capsys):
    """Tier-1 pin of the roofline-gap bench contract (schema
    roofline_gap/v1): >= 2 (model, mesh) configs, a measured/predicted
    ratio for EVERY cost-model term (with honest exercised flags), a
    roofline_calib/v1 calibration record, and a gpt tok/s arc for the
    perf_accounting fold. No absolute-ratio gate — CPU interpret ratios
    are astronomically off the v5e prediction by design; the pin is
    presence + finiteness + positivity."""
    import json

    import numpy as np

    from edl_tpu.tools import roofline_gap

    rc = roofline_gap.main(["--micro", "--iters", "1"])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1
    doc = json.loads(lines[-1])
    assert doc["schema"] == "roofline_gap/v1"
    assert doc["mode"] == "micro"
    assert set(doc["chip_builtin"]) >= {"bf16_tflops", "hbm_gbps",
                                        "ici_gbps"}
    configs = doc["configs"]
    assert len(configs) >= 2
    assert {c["model"] for c in configs} == {"gpt", "bert"}
    terms = set(roofline_gap.RATIO_TERMS)
    for cfg in configs:
        assert "error" not in cfg, cfg
        assert cfg["world"] >= 2
        assert set(cfg["ratios"]) == terms
        assert set(cfg["exercised"]) == terms
        for term, r in cfg["ratios"].items():
            assert np.isfinite(r) and r > 0, (cfg["name"], term, r)
        # unexercised terms report the neutral ratio, not a fake fit
        for term, on in cfg["exercised"].items():
            if not on and term not in ("compute", "hbm"):
                assert cfg["ratios"][term] == 1.0, (cfg["name"], term)
        assert cfg["measured"]["total_s"] > 0
        assert cfg["predicted"]["total_s"] > 0
        assert cfg["tokens_per_sec_per_chip"] > 0
    # the dp term was actually measured on these meshes
    assert any(c["exercised"]["dp"] for c in configs)
    # the accum-over-dp config swept the overlap schedule
    overlaps = [c["overlap"] for c in configs if c["overlap"]]
    assert overlaps and all(o["off_s"] > 0 and o["on_s"] > 0
                            for o in overlaps)
    calib = doc["calibration"]
    assert calib["schema"] == "roofline_calib/v1"
    assert isinstance(calib["chip"], dict)
    for field, val in calib["chip"].items():
        if field == "name":
            continue
        assert np.isfinite(val) and val > 0, (field, val)
    arc = doc["gpt_arc"]
    assert arc and arc["value"] > 0
    assert arc["unit"] == "tok/s/chip"
    assert arc["platform"] == "cpu"
    json.dumps(doc)  # round-trips


def test_roofline_calib_round_trip_and_fail_open(tmp_path, monkeypatch):
    """costmodel loads a roofline_calib/v1 record via the env var and
    plans against the fitted constants; a missing, corrupt, or
    out-of-sanity-bounds record keeps the builtin CHIP_V5E values
    PER FIELD (fail-open proven, the acceptance bullet)."""
    import json

    from edl_tpu.parallel import costmodel

    prof = costmodel.transformer_profile(n_layers=8, d_model=1024,
                                         n_heads=16, seq_len=512)
    factors = {"dp": 4, "tp": 1, "pp": 1, "ep": 1}

    # no calibration installed: defaults ARE the builtins
    monkeypatch.delenv(costmodel.CALIB_ENV, raising=False)
    assert costmodel.calibrated_chip() == costmodel.CHIP_V5E

    # round trip: fitted constants flow into default-chip scoring
    good = tmp_path / "calib_good.json"
    good.write_text(json.dumps({
        "schema": costmodel.CALIB_SCHEMA,
        "chip": {"name": "v5e+fit", "bf16_tflops": 150.0,
                 "hbm_gbps": 700.0, "ici_gbps": 90.0}}))
    monkeypatch.setenv(costmodel.CALIB_ENV, str(good))
    chip = costmodel.calibrated_chip()
    assert chip["bf16_tflops"] == 150.0
    assert chip["hbm_gbps"] == 700.0
    assert chip["ici_gbps"] == 90.0
    t_cal = costmodel.step_time_s(factors, prof, total_batch=64)
    t_builtin = costmodel.step_time_s(factors, prof, total_batch=64,
                                      chip=costmodel.CHIP_V5E)
    # slower fitted ICI -> a larger dp term under the default chip
    assert t_cal["dp_s"] > t_builtin["dp_s"]

    # corrupt file: whole record dropped, builtins stay
    bad = tmp_path / "calib_corrupt.json"
    bad.write_text("{not json")
    monkeypatch.setenv(costmodel.CALIB_ENV, str(bad))
    assert costmodel.calibrated_chip() == costmodel.CHIP_V5E

    # wrong schema: dropped
    wrong = tmp_path / "calib_wrong_schema.json"
    wrong.write_text(json.dumps({"schema": "nope/v9",
                                 "chip": {"bf16_tflops": 150.0}}))
    monkeypatch.setenv(costmodel.CALIB_ENV, str(wrong))
    assert costmodel.calibrated_chip() == costmodel.CHIP_V5E

    # out-of-bounds field dropped PER FIELD, sane sibling kept (a CPU
    # micro fit must not brick the planner's compute constant)
    partial = tmp_path / "calib_partial.json"
    partial.write_text(json.dumps({
        "schema": costmodel.CALIB_SCHEMA,
        "chip": {"bf16_tflops": 0.001, "hbm_gbps": 700.0,
                 "ici_gbps": float("nan")}}))
    monkeypatch.setenv(costmodel.CALIB_ENV, str(partial))
    chip = costmodel.calibrated_chip()
    assert chip["bf16_tflops"] == costmodel.CHIP_V5E["bf16_tflops"]
    assert chip["hbm_gbps"] == 700.0
    assert chip["ici_gbps"] == costmodel.CHIP_V5E["ici_gbps"]

    # missing path: builtins
    monkeypatch.setenv(costmodel.CALIB_ENV, str(tmp_path / "gone.json"))
    assert costmodel.calibrated_chip() == costmodel.CHIP_V5E


def test_fold_roofline_gap_updates_best(tmp_path):
    """perf_accounting folds a roofline_gap/v1 gpt arc into the
    BENCH_BEST pointer: vs_baseline computed against the 59,157.8
    baseline, source stamped, non-TPU arcs refused — the headline can
    never silently sit at 0.0 again."""
    import json

    from edl_tpu.tools import perf_accounting as pa

    best = tmp_path / "best.json"
    best.write_text(json.dumps({"gpt": {
        "metric": "gpt2s_train_tokens_per_sec_per_chip",
        "value": 59157.8, "unit": "tok/s/chip", "vs_baseline": 0.0,
        "measured": "2026-07-31", "source": "pre-PR-1 chip sweep"}}))

    def gap(platform, value):
        return {"schema": "roofline_gap/v1",
                "gpt_arc": {"metric": "gpt_train_tokens_per_sec_per_chip",
                            "value": value, "unit": "tok/s/chip",
                            "platform": platform, "config": "gpt2s_dp_all",
                            "measured": "2026-08-08"}}

    # a CPU arc is refused outright (the pointer stays TPU-measured)
    changed, msg = pa.fold_roofline_gap(gap("cpu", 999999.0), str(best))
    assert not changed and "refusing" in msg
    assert json.loads(best.read_text())["gpt"]["value"] == 59157.8

    # a slower TPU arc does not regress the best value, but the stale
    # 0.0 vs_baseline is backfilled
    changed, msg = pa.fold_roofline_gap(gap("tpu", 50000.0), str(best))
    assert changed
    rec = json.loads(best.read_text())["gpt"]
    assert rec["value"] == 59157.8
    assert rec["vs_baseline"] == 1.0
    assert rec["baseline"] == pa.BASELINES["gpt"]

    # a faster TPU arc takes the record and stamps its source
    changed, msg = pa.fold_roofline_gap(gap("tpu", 70989.4), str(best))
    assert changed
    rec = json.loads(best.read_text())["gpt"]
    assert rec["value"] == 70989.4
    assert rec["source"].startswith("roofline_gap/v1 gpt2s_dp_all")
    assert rec["vs_baseline"] == round(70989.4 / 59157.8, 3)

    # idempotent: same arc again changes nothing
    changed, _ = pa.fold_roofline_gap(gap("tpu", 70989.4), str(best))
    assert not changed

    # malformed docs are rejected, not half-applied
    changed, msg = pa.fold_roofline_gap({"schema": "other/v1"}, str(best))
    assert not changed
    changed, msg = pa.fold_roofline_gap({"schema": "roofline_gap/v1",
                                         "gpt_arc": None}, str(best))
    assert not changed


def test_decode_bench_micro_schema():
    """Tier-1 pin of the decode bench contract (schema decode_bench/v1):
    micro mode must prove the serving-decode guarantees end to end —
    continuous batching is token-identical to ``gpt.generate`` (serial,
    batched, and int8 engines) under ONE fused step trace (speed-ups
    are reported, not gated: CPU wall-clock ratios under load say
    nothing about the chip); every decode shed reason fires typed
    with zero admitted sequences stranded; slot saturation drives a
    journaled scale-out whose drain also strands nothing; the int8
    teacher passes the logits parity gate at half the weight bytes;
    shared-prefix reuse gives identical tokens and exact reuse
    accounting; and chunked prefill keeps ONE step trace and no
    prefill trace through a prompt storm.
    The parity and zero-stranded fields are MANDATORY: a report without
    them is a schema break, not a passing run."""
    import json

    from edl_tpu.serve.admission import DECODE_SHED_REASONS
    from edl_tpu.tools import serve_bench

    out = serve_bench.run_decode(mode="micro", seed=7)
    assert out["schema"] == "decode_bench/v1"

    # token parity: continuous batching NEVER changes the decode
    assert out["parity"]["serial_vs_generate_ok"] is True
    assert out["parity"]["cb_vs_generate_ok"] is True
    assert out["parity"]["int8_tokens_match"] is True

    # both arcs ran, under fixed-shape discipline
    assert out["throughput"]["speedup"] > 0
    assert out["throughput"]["cb_tokens_per_s"] > 0
    assert out["compile"]["step_traces"] == 1
    assert out["latency_ms"]["ttft_p50"] > 0
    assert out["latency_ms"]["itl_p50"] > 0

    # every decode-phase shed reason fired, typed; nothing admitted
    # was stranded
    assert out["shed"]["reasons_covered"] == sorted(DECODE_SHED_REASONS)
    assert sum(out["shed"]["by_reason"].values()) >= \
        len(DECODE_SHED_REASONS)
    assert out["shed"]["stranded"] == 0

    # pinned slots forced a journaled scale-out; the fleet drained with
    # zero stranded sequences
    assert out["scale_out"]["engines"] >= 2
    assert out["scale_out"]["scale_out"] >= 1
    assert out["scale_out"]["journaled"] >= 1
    assert out["scale_out"]["zero_stranded"] is True

    # the quantization gate: close logits, genuinely smaller teacher
    assert out["quant"]["int8_logits_rel_err"] < 0.05
    assert out["quant"]["int8_bytes_ratio"] < 0.6

    # shared-prefix reuse at >= 50% overlap: tokens IDENTICAL to cold
    # prefill, and token-exact reuse accounting
    assert out["prefix"]["overlap_frac"] >= 0.5
    assert out["prefix"]["ttft_speedup"] > 0
    assert out["prefix"]["parity_ok"] is True
    assert out["prefix"]["accounting_exact"] is True
    assert out["prefix"]["hits"] >= 1

    # chunked prefill under the same fixed-shape discipline. Its two
    # verdicts compare p99 gaps of CPU wall-clock runs against twice a
    # baseline's: reported, not gated (``monolithic_exceeds_2x`` read
    # False in the driver's six-worker run of PR 42's tree)
    assert isinstance(out["chunked"]["chunked_within_2x"], bool)
    assert isinstance(out["chunked"]["monolithic_exceeds_2x"], bool)
    assert out["chunked"]["baseline_itl_p99"] > 0
    assert out["chunked"]["chunked_itl_p99"] > 0
    assert out["chunked"]["monolithic_itl_p99"] > 0
    assert out["chunked"]["step_traces"] == 1
    assert out["chunked"]["prefill_traces"] == 0
    assert out["chunked"]["chunk_traces"] <= 2

    json.dumps(out)  # the whole report is JSON-serializable


def test_rec_bench_micro_schema_and_gates():
    """The sharded-embedding bench must keep working in a tiny CPU
    config under tier-1 and honor its JSON contract (schema
    rec_bench/v1). Unlike the other bench pins, this one DOES gate the
    arcs: the dedup+hot-cache arc replaces per-slot RPCs with one
    coalesced gather per owner, so its >=1.5x floor over the naive arc
    has order-of-magnitude headroom (~19x on an idle box) and holds on
    a noisy CI host; overlap must strictly cut embed_wait vs its
    no-overlap twin; and the mid-run reshard must leave the stitched
    table byte-identical to stop-resume."""
    import json

    from edl_tpu.tools import rec_bench

    out = rec_bench.run(mode="micro")
    assert out["schema"] == "rec_bench/v1"
    for arc in ("naive", "dedup", "dedup_cache", "overlap"):
        a = out["arcs"][arc]
        assert a["rows_s"] > 0
        assert a["lookup_ms_p99"] >= a["lookup_ms_p50"] >= 0
        assert a["retries"] == 0  # no chaos in the bench
    assert out["arcs"]["naive"]["unique_key_frac"] == 1.0
    assert out["arcs"]["dedup"]["unique_key_frac"] < 1.0  # zipf head
    cached = out["arcs"]["dedup_cache"]
    assert 0 <= cached["cache_hit_rate"] <= 1
    assert 0 < out["predicted_head_mass"] <= 1
    # the three acceptance gates ride tier-1
    assert out["speedup_dedup_cache_vs_naive"] >= 1.5
    assert out["arcs"]["overlap"]["embed_wait_s"] \
        < out["arcs"]["dedup_cache"]["embed_wait_s"]
    assert out["resize"]["identical_ok"] is True
    assert out["resize"]["members_from"] == 2
    assert out["resize"]["members_to"] == 3
    assert out["gates"] == {"speedup_ok": True, "overlap_ok": True,
                            "identical_ok": True}
    assert out["ok"] is True
    json.dumps(out)  # the whole report is JSON-serializable
