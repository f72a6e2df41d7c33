"""Distributed training supervisor: heartbeats, collective watchdog,
elastic restart (parallel/heartbeat.py, lightgbm_tpu/supervisor.py).

The in-process tests exercise the primitives with injected callbacks;
the subprocess tests run REAL two-process jax.distributed training on
CPU (gloo collectives) and prove the acceptance path end to end: a
rank killed mid-iteration is detected within `heartbeat_timeout_s`, the
supervisor restarts from the newest shared snapshot, and the final
model is byte-identical to an uninterrupted run of the same topology.
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from lightgbm_tpu.parallel import heartbeat as hb
from lightgbm_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(__file__))


# ------------------------------------------------------------- heartbeats

def test_heartbeat_publish_and_expiry(tmp_path):
    lost = []
    s0 = hb.HeartbeatService(tmp_path, 0, 2, timeout_s=0.5,
                             interval_s=0.1, on_peer_lost=lost.append)
    s1 = hb.HeartbeatService(tmp_path, 1, 2, timeout_s=0.5, interval_s=0.1)
    s1.publish()
    s0.publish()
    beats = s0.scan()
    assert beats[1]["rank"] == 1 and beats[1]["seq"] == 1
    assert s0.dead_peers() == []
    # rank 1 keeps beating -> stays alive past the timeout window
    deadline = time.monotonic() + 0.8
    while time.monotonic() < deadline:
        s1.publish()
        s0.check_once()
        time.sleep(0.1)
    assert s0.dead_peers() == [] and not lost
    # rank 1 goes silent -> declared dead after timeout_s, callback once
    deadline = time.monotonic() + 3.0
    while not lost and time.monotonic() < deadline:
        s0.check_once()
        time.sleep(0.1)
    assert lost == [[1]]
    assert s0.peer_ages()[1] > 0.5


def test_heartbeat_missing_peer_gets_startup_grace_then_dies(tmp_path):
    # a peer that NEVER publishes (crashed pre-start / stale dir) is
    # dead one timeout after monitor start, not instantly
    s0 = hb.HeartbeatService(tmp_path, 0, 2, timeout_s=0.4,
                             interval_s=0.1, on_peer_lost=lambda r: None)
    s0.scan()
    assert s0.dead_peers() == []
    time.sleep(0.6)
    s0.scan()
    assert s0.dead_peers() == [1]


def test_heartbeat_done_rank_never_declared_dead(tmp_path):
    s0 = hb.HeartbeatService(tmp_path, 0, 2, timeout_s=0.3, interval_s=0.1)
    s1 = hb.HeartbeatService(tmp_path, 1, 2, timeout_s=0.3, interval_s=0.1)
    s1.publish(done=True)  # rank 1 finished cleanly
    time.sleep(0.5)
    s0.scan()
    assert s0.dead_peers() == []


def test_heartbeat_stale_fault_suppresses_publish(tmp_path):
    s1 = hb.HeartbeatService(tmp_path, 1, 2, timeout_s=0.5, interval_s=0.1)
    with faults.injected_faults(heartbeat_stale=1):
        s1.publish()
    assert not os.path.exists(hb.heartbeat_path(tmp_path, 1))
    # other ranks are unaffected
    with faults.injected_faults(heartbeat_stale=1):
        s0 = hb.HeartbeatService(tmp_path, 0, 2, timeout_s=0.5,
                                 interval_s=0.1)
        s0.publish()
    assert os.path.exists(hb.heartbeat_path(tmp_path, 0))
    # -1 suppresses every rank
    with faults.injected_faults(heartbeat_stale=-1):
        s1.publish()
    assert not os.path.exists(hb.heartbeat_path(tmp_path, 1))


def test_heartbeat_beats_carry_snapshot_and_straggler_info(tmp_path):
    wd = hb.CollectiveWatchdog(0.0, rank=1)
    wd.last_sync_s = 2.5
    s1 = hb.HeartbeatService(tmp_path, 1, 2, timeout_s=1.0,
                             interval_s=0.1, watchdog=wd)
    s1.notify_snapshot(4, str(tmp_path / "snap"))
    s1.publish()
    beat = hb.read_heartbeat(hb.heartbeat_path(tmp_path, 1))
    assert beat["sync_s"] == 2.5 and beat["snapshot_iteration"] == 4
    s0 = hb.HeartbeatService(tmp_path, 0, 2, timeout_s=1.0, interval_s=0.1)
    report = s0.straggler_report(s0.scan())
    assert "rank 1 slowest" in report


# --------------------------------------------------------------- watchdog

def test_watchdog_fires_with_rank_iteration_collective(tmp_path):
    fired = []
    wd = hb.CollectiveWatchdog(0.2, rank=3, marker_dir=str(tmp_path),
                               on_expire=lambda n, i: fired.append((n, i)))
    wd.set_iteration(11)
    with wd.armed("hist_psum"):
        time.sleep(0.5)
    assert fired == [("hist_psum", 11)]
    import json
    with open(hb.watchdog_marker_path(tmp_path, 3)) as f:
        m = json.load(f)
    assert (m["rank"], m["collective"], m["iteration"]) == (3, "hist_psum",
                                                            11)
    # a fast sync cancels the timer and records the straggler timing
    with wd.armed("quick"):
        pass
    time.sleep(0.4)
    assert fired == [("hist_psum", 11)]
    assert wd.timings["hist_psum"] >= 0.2 and "quick" in wd.timings


def test_watchdog_disabled_is_free():
    # zero-overhead contract: disarmed AND no telemetry timing sink
    # bound -> no timer, no timings bookkeeping. (A bound sink makes
    # guarded sections measure even when disarmed — comm telemetry,
    # telemetry/comm_profile.py — so pin the unbound state first: a
    # leaked sink from an earlier telemetry run would break the free
    # path this test guards.)
    hb.bind_timing_sink(None)
    wd = hb.CollectiveWatchdog(0.0)
    with wd.armed("anything"):
        pass
    assert wd.timings == {}
    # and the flip side: binding a sink is what turns measurement on
    hb.bind_timing_sink(lambda name, s: None)
    try:
        with wd.armed("measured"):
            pass
    finally:
        hb.bind_timing_sink(None)
    assert "measured" in wd.timings


# ---------------------------------------------------------- rank faults

def test_rank_fault_spec_parsing():
    faults.set_fault("rank_crash_at_iteration", "1:3")
    assert faults._rank_iter_spec("rank_crash_at_iteration") == (1, 3)
    faults.set_fault("rank_crash_at_iteration", 5)
    assert faults._rank_iter_spec("rank_crash_at_iteration") == (None, 5)
    faults.set_fault("rank_crash_at_iteration", "bogus")
    assert faults._rank_iter_spec("rank_crash_at_iteration") is None
    faults.clear_faults()


def test_rank_faults_disarmed_on_restart_attempt(monkeypatch):
    # a supervisor relaunch (attempt > 0) must train through: the
    # injected event models ONE preemption, not a broken rank
    monkeypatch.setenv("LIGHTGBM_TPU_RESTART_ATTEMPT", "1")
    with faults.injected_faults(rank_crash_at_iteration="0:0",
                                rank_hang_at_iteration="0:0"):
        faults.set_rank(0)
        faults.rank_crash_if_reached(0)   # would os._exit(43) if armed
        faults.rank_hang_if_reached(0)    # would hang forever if armed
    faults._rank = None


def test_rank_crash_only_matching_rank(monkeypatch):
    monkeypatch.delenv("LIGHTGBM_TPU_RESTART_ATTEMPT", raising=False)
    with faults.injected_faults(rank_crash_at_iteration="1:3"):
        faults.set_rank(0)
        faults.rank_crash_if_reached(3)   # rank 0 must survive
    faults._rank = None


# --------------------------------------------------------- restart barrier

def test_restart_barrier_all_present(tmp_path):
    from lightgbm_tpu.supervisor import restart_barrier
    shared = str(tmp_path)
    # peer (rank 1) posted its marker already; rank 0 joins instantly
    from lightgbm_tpu.supervisor import _post_marker
    _post_marker(shared, 1, 1, 43)
    t0 = time.monotonic()
    survivors = restart_barrier(shared, 1, 0, [0, 1], wait_s=5.0)
    assert survivors == [0, 1]
    assert time.monotonic() - t0 < 2.0  # no full wait when all present


def test_restart_barrier_shrinks_after_wait(tmp_path):
    from lightgbm_tpu.supervisor import restart_barrier
    survivors = restart_barrier(str(tmp_path), 1, 0, [0, 1, 2],
                                wait_s=0.6)
    assert survivors == [0]


def test_describe_exit_codes():
    from lightgbm_tpu.supervisor import describe_exit
    assert "watchdog" in describe_exit(hb.EXIT_WATCHDOG)
    assert "peer" in describe_exit(hb.EXIT_PEER_LOST)
    assert "crash" in describe_exit(faults.HARD_CRASH_EXIT_CODE)
    assert "signal 9" in describe_exit(-9)


def test_format_machine_list_roundtrip(tmp_path):
    from lightgbm_tpu.parallel.machines import (format_machine_list,
                                                parse_machine_list)
    machines = [("10.0.0.1", 12400), ("2001:db8::1", 12401)]
    path = tmp_path / "m.txt"
    path.write_text(format_machine_list(machines))
    assert parse_machine_list(str(path)) == machines


# -------------------------------------------------- two-process end-to-end

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_data(path, n=1200, f=5):
    rng = np.random.RandomState(11)
    x = rng.rand(n, f)
    y = ((x[:, 0] + x[:, 1] * x[:, 2]) > 0.9).astype(int)
    np.savetxt(path, np.column_stack([y, x]), delimiter=",", fmt="%.6f")


def _base_args(tmp_path, tag, mlist, extra=()):
    return ["task=train", f"data={tmp_path / 'tr.csv'}",
            "objective=binary", "num_leaves=7", "num_iterations=6",
            "tree_learner=data", "num_machines=2",
            f"machine_list_file={mlist}", "min_data_in_leaf=10",
            "metric_freq=0", "enable_load_from_binary_file=false",
            "snapshot_freq=2",
            f"snapshot_dir={tmp_path / tag / 'snaps'}",
            f"output_model={tmp_path / tag / 'model.txt'}"] + list(extra)


def _rank_env(rank, fault_spec=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               LIGHTGBM_TPU_RANK=str(rank),
               PYTHONPATH=REPO)
    env.pop("LIGHTGBM_TPU_FAULTS", None)
    env.pop("LIGHTGBM_TPU_RESTART_ATTEMPT", None)
    if fault_spec:
        env["LIGHTGBM_TPU_FAULTS"] = fault_spec
    return env


def _launch(module, args, rank, fault_spec=None):
    return subprocess.Popen(
        [sys.executable, "-m", module] + args, cwd=REPO,
        env=_rank_env(rank, fault_spec), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _gang(tmp_path, tag, module, fault_specs, extra=(), timeout=300):
    """Run a 2-process gang; returns [(rc, output)] per rank."""
    (tmp_path / tag).mkdir(exist_ok=True)
    port = _free_port()
    mlist = tmp_path / f"mlist_{tag}.txt"
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    procs = [_launch(module, _base_args(tmp_path, tag, mlist, extra),
                     rank, fault_specs[rank]) for rank in range(2)]
    results = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT KILL>"
        results.append((p.returncode, out))
    return results


@pytest.mark.slow
def test_rank_crash_supervisor_restart_model_parity(tmp_path):
    """THE acceptance path: rank 1 is os._exit-killed at iteration 3;
    the surviving rank detects it within heartbeat_timeout_s (no
    indefinite hang), both supervisors meet at the restart barrier,
    relaunch, auto-resume from the newest shared snapshot, and the
    final model is byte-identical to an uninterrupted run of the same
    2-rank topology."""
    _write_data(tmp_path / "tr.csv")
    knobs = ("heartbeat_timeout_s=6", "collective_timeout_s=30",
             "max_restarts=2", "telemetry=true")
    ref = _gang(tmp_path, "ref", "lightgbm_tpu", [None, None], knobs)
    for rank, (rc, out) in enumerate(ref):
        assert rc == 0, f"ref rank {rank} failed:\n{out[-3000:]}"

    t0 = time.monotonic()
    sup = _gang(tmp_path, "crash", "lightgbm_tpu.supervisor",
                ["rank_crash_at_iteration=1:3"] * 2, knobs)
    elapsed = time.monotonic() - t0
    for rank, (rc, out) in enumerate(sup):
        assert rc == 0, f"supervisor rank {rank} failed:\n{out[-3000:]}"
    # the survivor did NOT hang: detection + restart + resumed tail
    # completes within a small multiple of the timeout knobs
    assert elapsed < 240, f"restart path took {elapsed:.0f}s"
    out0 = sup[0][1]
    assert "supervisor: restarting rank 0" in out0
    # detected (heartbeat monitor or collective error), then resumed
    assert ("declared dead" in out0 or "exited with code" in out0)
    assert "Resuming from checkpoint" in out0
    ref_model = (tmp_path / "ref" / "model.txt").read_text()
    crash_model = (tmp_path / "crash" / "model.txt").read_text()
    assert crash_model == ref_model  # byte-identical
    # the whole failure story is machine-readable in the merged run
    # journal: abort (the survivor's detection) -> supervisor restart
    # -> resume from the shared snapshot (telemetry/journal.py)
    from lightgbm_tpu.telemetry.journal import read_journal, validate_record
    merged = tmp_path / "crash" / "snaps" / "journal.jsonl"
    records, bad = read_journal(str(merged))
    assert bad == 0 and records
    for rec in records:
        assert validate_record(rec) == [], rec
    events = [rec["event"] for rec in records]
    assert any(rec["event"] == "abort"
               and rec["exit_code"] in (hb.EXIT_WATCHDOG,
                                        hb.EXIT_PEER_LOST)
               for rec in records)
    assert any(rec["event"] == "restart"
               and rec.get("source") == "supervisor" for rec in records)
    assert "resume" in events and "run_end" in events


@pytest.mark.slow
def test_watchdog_abort_names_hung_rank_iteration_collective(tmp_path):
    """A STRAGGLER (not a death): rank 1 sleeps forever at iteration 3
    while still heartbeating, so only the collective watchdog can save
    the survivor — it must abort with the distinct exit code and name
    the hung rank/iteration/collective in its log."""
    _write_data(tmp_path / "tr.csv")
    results = _gang(tmp_path, "hang", "lightgbm_tpu",
                    ["rank_hang_at_iteration=1:3"] * 2,
                    ("heartbeat_timeout_s=30", "collective_timeout_s=6",
                     "telemetry=true"),
                    timeout=120)
    rc0, out0 = results[0]
    assert rc0 == hb.EXIT_WATCHDOG, out0[-3000:]
    assert "collective watchdog expired: rank 0" in out0
    assert "at iteration 3" in out0
    # the collective is named (whichever armed sync point the async
    # dispatch surfaced the wait at — data:* or leaf_count_sync)
    assert "hung in '" in out0
    # the marker file records the same diagnosis for the supervisor
    import json
    marker = hb.watchdog_marker_path(
        tmp_path / "hang" / "snaps" / "heartbeats", 0)
    with open(marker) as f:
        m = json.load(f)
    assert m["iteration"] == 3 and m["collective"]
    # the hung rank terminated too (its own monitor saw rank 0 die, or
    # the distributed runtime aborted it) — nothing left to leak
    assert results[1][0] != 0
    # the abort is in the journal with the same diagnosis the marker
    # carries — written just before os._exit(117)
    from lightgbm_tpu.telemetry.journal import journal_path, read_journal
    records, bad = read_journal(
        journal_path(tmp_path / "hang" / "snaps", 0))
    assert bad == 0
    abort = next(rec for rec in records if rec["event"] == "abort")
    assert abort["exit_code"] == hb.EXIT_WATCHDOG
    assert abort["iteration"] == 3 and abort["collective"]


@pytest.mark.slow
def test_shrunken_world_restart_smoke(tmp_path):
    """Rank 1 dies and NEVER comes back (no supervisor on its machine):
    rank 0's supervisor times out waiting at the restart barrier,
    shrinks the world to 1 rank, re-partitions the rows, resumes from
    the shared snapshot's GLOBAL score, and finishes a valid model."""
    _write_data(tmp_path / "tr.csv")
    (tmp_path / "shrink").mkdir()
    port = _free_port()
    mlist = tmp_path / "mlist_shrink.txt"
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    args = _base_args(tmp_path, "shrink", mlist,
                      ("heartbeat_timeout_s=5", "max_restarts=2"))
    p0 = _launch("lightgbm_tpu.supervisor", args, 0)
    p1 = _launch("lightgbm_tpu", args, 1, "rank_crash_at_iteration=1:3")
    out1, _ = p1.communicate(timeout=200)
    assert p1.returncode == faults.HARD_CRASH_EXIT_CODE, out1[-2000:]
    out0, _ = p0.communicate(timeout=200)
    assert p0.returncode == 0, out0[-3000:]
    assert "shrinking the world to 1 rank(s)" in out0
    assert "Resuming from checkpoint" in out0
    # pre-shrink the meshed learner announced its 4-shard topology
    # (2 procs x 2 virtual devices); the relaunch shrank to ONE
    # machine, which check_param_conflict coerces to the serial
    # learner — the mesh itself was re-derived, not just the list
    assert "mesh: 4 shard(s) x 2 process(es)" in out0
    model = (tmp_path / "shrink" / "model.txt").read_text()
    assert model.count("Tree=") == 6  # resumed past the crash to the end
