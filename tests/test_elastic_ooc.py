"""Elastic out-of-core gang training (ISSUE 18).

Three layers:

- ownership math + views (fast): the jax-free contiguous block
  partition (parallel/machines.py), its MeshTopology surface, the
  shared-store gang dataset views (data/block_store.py gang_view_of),
  and the W=1 gang learner's bit-parity with the serial out-of-core
  learner (the degenerate exchange);
- resume safety (fast): post-restart store re-verification
  (BlockStore.reverify + the `bitrot_block_on_restart` fault), the
  manifest `build_count` re-bin ledger, the torn mid-checkpoint-write
  preemption, the `block_reshard`/`binning` journal events, and the
  supervisor's grow-back helper;
- chaos rungs (slow): REAL two-process gloo gangs over ONE shared
  block store — a rank killed mid-prefetch shrinks the world with zero
  re-binning; a rank killed mid-iteration shrinks and the survivor's
  resumed model is byte-identical to a single-rank run resumed from
  the SAME snapshot; a same-topology restart reproduces the
  uninterrupted gang's model byte for byte.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import BlockStoreError, spill_core_dataset
from lightgbm_tpu.data.block_store import (MANIFEST_NAME, gang_view_of,
                                           load_block_store_gang)
from lightgbm_tpu.data.ooc_learner import OutOfCoreTreeLearner
from lightgbm_tpu.data.ooc_parallel import OutOfCoreGangLearner
from lightgbm_tpu.io.dataset import DatasetLoader
from lightgbm_tpu.parallel.machines import (check_block_tiling,
                                            partition_blocks)
from lightgbm_tpu.parallel.mesh import MeshTopology
from lightgbm_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(__file__))

OOC = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
       "verbose": -1, "hist_compaction": "false", "device_row_chunk": 256,
       "out_of_core": True, "block_rows": 512}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear_faults()
    yield
    faults.clear_faults()
    faults._rank = None


def _data(n=3000, f=8, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f)
    y = (x[:, 0] + 0.6 * x[:, 1] * x[:, 2]
         + 0.8 * rng.randn(n) > 0).astype(np.float64)
    return x, y


def _spilled(tmp_path, n=3000, block_rows=512):
    x, y = _data(n=n)
    core = DatasetLoader(Config.from_params({"verbose": -1})) \
        .construct_from_matrix(x, label=y)
    return spill_core_dataset(core, str(tmp_path / "st"), block_rows)


# ====================================================== ownership math

def test_partition_blocks_tiles_exactly():
    for num_blocks in (0, 1, 2, 5, 7, 16, 33):
        for world in (1, 2, 3, 4, 7):
            ranges = [partition_blocks(num_blocks, world, r)
                      for r in range(world)]
            check_block_tiling(ranges, num_blocks)  # no gaps, no overlap
            sizes = [hi - lo for lo, hi in ranges]
            assert max(sizes) - min(sizes) <= 1  # balanced
            assert sorted(sizes, reverse=True) == sizes  # earlier >= later


def test_check_block_tiling_rejects_bad_leases():
    with pytest.raises(ValueError, match="stale block-ownership lease"):
        check_block_tiling([(0, 4), (5, 10)], 10)          # gap
    with pytest.raises(ValueError, match="stale block-ownership lease"):
        check_block_tiling([(0, 6), (4, 10)], 10)          # overlap
    with pytest.raises(ValueError, match="stale block-ownership lease"):
        check_block_tiling([(0, 4), (4, 8)], 10)           # undercover
    with pytest.raises(ValueError, match="stale block-ownership lease"):
        check_block_tiling([(0, 4), (4, 3)], 10)           # inverted


def test_topology_owned_block_range_matches_partition():
    # pure ownership math off the topology surface — n_proc is the
    # only field owned_block_range consults, so pin it directly
    # rather than standing up a 4-process mesh
    topo = MeshTopology.__new__(MeshTopology)
    topo.n_proc = 4
    for shard in range(4):
        assert topo.owned_block_range(shard, 10) == \
            partition_blocks(10, 4, shard)


def test_stale_ownership_fault_widens_world():
    faults.set_rank(1)
    assert faults.stale_ownership_world(2) == 2
    with faults.injected_faults(stale_ownership=1):
        assert faults.stale_ownership_world(2) == 3
    with faults.injected_faults(stale_ownership=0):  # other rank armed
        assert faults.stale_ownership_world(2) == 2
    with faults.injected_faults(stale_ownership=-1):  # every rank
        assert faults.stale_ownership_world(2) == 3


# ========================================================== gang views

def test_gang_view_two_ranks_partition_rows_and_bins(tmp_path):
    ds = _spilled(tmp_path, n=3000, block_rows=512)  # 6 blocks, last=440
    v0 = gang_view_of(ds, 0, 2)
    v1 = gang_view_of(ds, 1, 2)
    assert (v0.block_lo, v0.block_hi) == (0, 3)
    assert (v1.block_lo, v1.block_hi) == (3, 6)
    assert v0.num_data + v1.num_data == 3000
    assert v0.num_data == 3 * 512
    assert np.array_equal(
        np.concatenate([v0.metadata.label, v1.metadata.label]),
        ds.metadata.label)
    # local traversal rows resolve to the shared store's global rows
    whole = ds.traversal_bins()
    part = v1.traversal_bins()
    rows = np.arange(0, v1.num_data, 97)
    feats = np.zeros_like(rows)
    assert np.array_equal(part[feats, rows],
                          whole[feats, rows + v1.row_lo])


def test_gang_view_stale_world_breaks_tiling(tmp_path):
    ds = _spilled(tmp_path, n=3000, block_rows=512)
    faults.set_rank(1)
    with faults.injected_faults(stale_ownership=1):
        stale = gang_view_of(ds, 1, 2)   # derived from a world of 3
    fresh0 = gang_view_of(ds, 0, 2)
    with pytest.raises(ValueError, match="stale block-ownership lease"):
        check_block_tiling([(fresh0.block_lo, fresh0.block_hi),
                            (stale.block_lo, stale.block_hi)], 6)


def test_gang_learner_single_rank_bit_parity(tmp_path):
    """The degenerate exchange: a one-rank gang must produce the SAME
    tree, bit for bit, as the serial out-of-core learner (same Kahan
    carries, same collapse)."""
    ds = _spilled(tmp_path)
    cfg = Config.from_params(dict(OOC))
    rng = np.random.RandomState(7)
    g = rng.randn(3000).astype(np.float32)
    h = (rng.rand(3000) + 0.2).astype(np.float32)
    serial = OutOfCoreTreeLearner(cfg)
    serial.init(ds)
    out_ref = serial.train_device(g, h)
    gang = OutOfCoreGangLearner(cfg)
    gang.init(gang_view_of(ds, 0, 1))
    assert (gang._blk_lo, gang._blk_hi) == (0, ds.block_store.num_blocks)
    out = gang.train_device(g, h)
    for key in out_ref:
        assert np.array_equal(np.asarray(out_ref[key]),
                              np.asarray(out[key])), key
    assert gang._gang_shape() == (1, 0)


def test_gang_load_peer_times_out_without_rank0_build(tmp_path):
    cfg = Config.from_params(dict(OOC, ooc_build_wait_s=0.3,
                                  ooc_dir=str(tmp_path / "never")))
    loader = DatasetLoader(cfg)
    t0 = time.monotonic()
    with pytest.raises(BlockStoreError, match="ooc_build_wait_s"):
        load_block_store_gang(loader, str(tmp_path / "absent.csv"), 1, 2)
    assert time.monotonic() - t0 < 10.0


# ================================================ restart resume safety

def _corrupt_last_byte(path):
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        byte = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([byte[0] ^ 0xFF]))


def test_reverify_detects_bitrot_and_restores_verify_flag(tmp_path):
    ds = _spilled(tmp_path, n=1500, block_rows=512)
    store = ds.block_store
    store.reverify(0, store.num_blocks)  # clean store passes
    store.verify = False
    _corrupt_last_byte(os.path.join(store.directory, "block-00001.npy"))
    with pytest.raises(BlockStoreError, match="block-00001.npy"):
        store.reverify(0, store.num_blocks)
    assert store.verify is False  # opt-out preserved after the sweep
    # a range that does not cover the rotted block stays green
    store.reverify(2, store.num_blocks)


def test_bitrot_fault_fires_only_on_restarted_attempt(tmp_path,
                                                      monkeypatch):
    ds = _spilled(tmp_path, n=1500, block_rows=512)
    store = ds.block_store
    monkeypatch.delenv("LIGHTGBM_TPU_RESTART_ATTEMPT", raising=False)
    with faults.injected_faults(bitrot_block_on_restart=1):
        store.reverify(0, store.num_blocks)  # attempt 0: no rot
    monkeypatch.setenv("LIGHTGBM_TPU_RESTART_ATTEMPT", "1")
    with faults.injected_faults(bitrot_block_on_restart=1):
        with pytest.raises(BlockStoreError, match="block-00001.npy"):
            store.reverify(0, store.num_blocks)


def test_learner_reverifies_owned_blocks_on_restart(tmp_path, monkeypatch):
    ds = _spilled(tmp_path, n=1500, block_rows=512)
    _corrupt_last_byte(os.path.join(ds.block_store.directory,
                                    "block-00000.npy"))
    ds.block_store.verify = False
    learner = OutOfCoreTreeLearner(Config.from_params(dict(OOC)))
    monkeypatch.setenv("LIGHTGBM_TPU_RESTART_ATTEMPT", "1")
    with pytest.raises(BlockStoreError, match="block-00000.npy"):
        learner.init(ds)
    # a fresh (attempt 0) incarnation skips the sweep: the per-read
    # crc path owns first-use detection there
    monkeypatch.delenv("LIGHTGBM_TPU_RESTART_ATTEMPT")
    learner2 = OutOfCoreTreeLearner(Config.from_params(dict(OOC)))
    learner2.init(ds)


def test_crash_mid_checkpoint_write_leaves_torn_tmp_only(tmp_path):
    """Preemption landing INSIDE the atomic checkpoint write: half the
    payload in the sibling tmp file, process dead before the rename —
    the final file must not exist, and a rerun must save + resume
    cleanly past the debris."""
    d = str(tmp_path / "ck")
    code = ("import numpy as np\n"
            "from lightgbm_tpu.utils.checkpoint import CheckpointManager\n"
            f"m = CheckpointManager({d!r}, keep_last_k=3)\n"
            "m.save({'state_version': 1, 'arr': np.arange(64)}, 2)\n")
    env = dict(os.environ, LIGHTGBM_TPU_FAULTS="crash_in_checkpoint_write=1")
    env.pop("LIGHTGBM_TPU_RESTART_ATTEMPT", None)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == faults.HARD_CRASH_EXIT_CODE, r.stdout + r.stderr
    names = os.listdir(d)
    assert not any(n.endswith(".ckpt") for n in names)
    assert any(".tmp." in n for n in names)  # the torn half-write
    env.pop("LIGHTGBM_TPU_FAULTS")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    from lightgbm_tpu.utils.checkpoint import CheckpointManager
    state, path = CheckpointManager(d).load_latest()
    assert state is not None and path.endswith(".ckpt")
    assert np.array_equal(state["arr"], np.arange(64))


def test_manifest_build_count_ledger(tmp_path):
    """`build_count` is the durable zero-re-bin proof: 1 after the
    first build, unchanged on signature-matching reuse, incremented
    only by an actual re-binning pass."""
    x, y = _data(n=900, f=5)
    data = str(tmp_path / "t.csv")
    np.savetxt(data, np.column_stack([y, x]), delimiter=",", fmt="%.6f")
    manifest = os.path.join(data + ".blocks", MANIFEST_NAME)

    def build_count():
        with open(manifest) as f:
            return json.load(f)["build_count"]

    cfg = Config.from_params(dict(OOC, verbose=-1))
    DatasetLoader(cfg).load_from_file(data)
    assert build_count() == 1
    DatasetLoader(cfg).load_from_file(data)      # reuse
    assert build_count() == 1
    cfg2 = Config.from_params(dict(OOC, verbose=-1, max_bin=63))
    DatasetLoader(cfg2).load_from_file(data)     # binning change
    assert build_count() == 2


def test_block_reshard_journal_event_emitted(tmp_path):
    """Every learner incarnation journals its owned range once; the
    serial learner reports a world of one covering the whole store."""
    from lightgbm_tpu.telemetry.journal import read_journal, validate_record
    x, y = _data(n=1500)
    params = dict(OOC, telemetry=True, telemetry_dir=str(tmp_path / "tj"))
    booster = lgb.train(dict(params), lgb.Dataset(x, y, params=dict(params)),
                        num_boost_round=2, verbose_eval=False)
    records, bad = read_journal(booster.gbdt.journal.path)
    assert bad == 0
    reshard = [r for r in records if r.get("event") == "block_reshard"]
    assert len(reshard) == 1
    rec = reshard[0]
    assert validate_record(rec) == []
    store = booster.gbdt.tree_learner.train_set.block_store
    assert rec["shards"] == 1 and rec["rank"] == 0
    assert (rec["block_lo"], rec["block_hi"]) == (0, store.num_blocks)
    assert rec["rows"] == 1500 and rec["attempt"] == 0


def test_binning_journal_event_schema():
    from lightgbm_tpu.telemetry.journal import validate_record
    assert validate_record({"event": "binning", "ts": 1.0, "mono": 1.0,
                            "rank": 0, "rows": 100, "blocks": 4,
                            "build_count": 2}) == []
    assert validate_record({"event": "binning", "ts": 1.0, "mono": 1.0,
                            "rank": 0, "rows": 100}) != []  # blocks required


def test_returned_ranks_grow_back_helper(tmp_path):
    from lightgbm_tpu.supervisor import _post_marker, returned_ranks
    shared = str(tmp_path)
    # world shrank from [0,1,2] to [0,2]; rank 1's machine comes back
    # and posts at attempt 2 — it rejoins; nothing else does
    assert returned_ranks(shared, 2, [0, 1, 2], [0, 2]) == []
    _post_marker(shared, 2, 1, 0)
    assert returned_ranks(shared, 2, [0, 1, 2], [0, 2]) == [1]
    # a marker from an older attempt does not count at attempt 3
    assert returned_ranks(shared, 3, [0, 1, 2], [0, 2]) == []
    # current members are never re-listed
    assert returned_ranks(shared, 2, [0, 1, 2], [0, 1, 2]) == []


# ============================================= two-process chaos rungs

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_gang_data(path, n=2048, f=5):
    rng = np.random.RandomState(11)
    x = rng.rand(n, f)
    y = ((x[:, 0] + x[:, 1] * x[:, 2]) > 0.9).astype(int)
    np.savetxt(path, np.column_stack([y, x]), delimiter=",", fmt="%.6f")


def _gang_args(tmp_path, tag, mlist, extra=()):
    return ["task=train", f"data={tmp_path / 'tr.csv'}",
            "objective=binary", "num_leaves=7", "num_iterations=6",
            "tree_learner=data", "num_machines=2", "out_of_core=true",
            "block_rows=512", "device_row_chunk=256",
            "hist_compaction=false", f"machine_list_file={mlist}",
            "min_data_in_leaf=10", "metric_freq=0",
            "enable_load_from_binary_file=false", "snapshot_freq=2",
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


def _gang(tmp_path, tag, modules, fault_specs, extra=(), timeout=420):
    (tmp_path / tag).mkdir(exist_ok=True)
    port = _free_port()
    mlist = tmp_path / f"mlist_{tag}.txt"
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    procs = [_launch(modules[rank], _gang_args(tmp_path, tag, mlist, extra),
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


def _run_single(tmp_path, tag, extra=(), timeout=420):
    (tmp_path / tag).mkdir(exist_ok=True)
    args = ["task=train", f"data={tmp_path / 'tr.csv'}",
            "objective=binary", "num_leaves=7", "num_iterations=6",
            "out_of_core=true", "block_rows=512", "device_row_chunk=256",
            "hist_compaction=false", "min_data_in_leaf=10",
            "metric_freq=0", "enable_load_from_binary_file=false",
            f"output_model={tmp_path / tag / 'model.txt'}"] + list(extra)
    p = _launch("lightgbm_tpu", args, 0)
    out, _ = p.communicate(timeout=timeout)
    return p.returncode, out


def _manifest_build_count(tmp_path):
    with open(tmp_path / "tr.csv.blocks" / MANIFEST_NAME) as f:
        return json.load(f)["build_count"]


KNOBS = ("heartbeat_timeout_s=6", "collective_timeout_s=30",
         "max_restarts=2", "telemetry=true")


@pytest.mark.slow
def test_gang_prefetch_kill_shrinks_without_rebinning(tmp_path):
    """Preemption in the prefetch in-flight window during the FIRST
    histogram pass (before any snapshot exists): the survivor's
    supervisor shrinks the world to one rank, the restart adopts every
    block of the shared store with the manifest's build_count still 1
    (zero re-binning), and the cold-started single-rank model equals a
    plain serial out-of-core run's."""
    _write_gang_data(tmp_path / "tr.csv")
    (tmp_path / "pf").mkdir()
    port = _free_port()
    mlist = tmp_path / "mlist_pf.txt"
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    p0 = _launch("lightgbm_tpu.supervisor",
                 _gang_args(tmp_path, "pf", mlist, KNOBS), 0)
    p1 = _launch("lightgbm_tpu", _gang_args(tmp_path, "pf", mlist, KNOBS),
                 1, "rank_crash_in_prefetch=1")
    out1, _ = p1.communicate(timeout=300)
    assert p1.returncode == faults.HARD_CRASH_EXIT_CODE, out1[-2000:]
    out0, _ = p0.communicate(timeout=300)
    assert p0.returncode == 0, out0[-4000:]
    assert "shrinking the world to 1 rank(s)" in out0
    assert _manifest_build_count(tmp_path) == 1
    ref_rc, ref_out = _run_single(tmp_path, "pf_ref")
    assert ref_rc == 0, ref_out[-2000:]
    assert (tmp_path / "pf" / "model.txt").read_text() == \
        (tmp_path / "pf_ref" / "model.txt").read_text()


@pytest.mark.slow
def test_gang_shrink_resume_matches_single_rank_from_same_snapshot(
        tmp_path):
    """THE elastic acceptance: rank 1 dies at iteration 3, rank 0's
    supervisor shrinks to one rank and resumes from the newest shared
    snapshot over the already-built store — zero re-binning
    (build_count still 1, no `binning` journal event), a
    `block_reshard` record with shards=1 on a restarted attempt, and
    the final model byte-identical to a single-rank run resumed from
    the SAME iteration-2 snapshot."""
    from lightgbm_tpu.telemetry.journal import read_journal
    _write_gang_data(tmp_path / "tr.csv")
    (tmp_path / "shrink").mkdir()
    port = _free_port()
    mlist = tmp_path / "mlist_shrink.txt"
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    args = _gang_args(tmp_path, "shrink", mlist, KNOBS)
    p0 = _launch("lightgbm_tpu.supervisor", args, 0)
    p1 = _launch("lightgbm_tpu", args, 1, "rank_crash_at_iteration=1:3")
    out1, _ = p1.communicate(timeout=300)
    assert p1.returncode == faults.HARD_CRASH_EXIT_CODE, out1[-2000:]
    out0, _ = p0.communicate(timeout=300)
    assert p0.returncode == 0, out0[-4000:]
    assert "shrinking the world to 1 rank(s)" in out0
    assert "Resuming from checkpoint" in out0
    model = (tmp_path / "shrink" / "model.txt").read_text()
    assert model.count("Tree=") == 6
    assert _manifest_build_count(tmp_path) == 1

    # journal: ownership re-derived on the restarted attempt, no re-bin
    records, bad = read_journal(
        str(tmp_path / "shrink" / "snaps" / "journal.jsonl"))
    assert bad == 0
    reshards = [r for r in records if r.get("event") == "block_reshard"]
    assert any(r["shards"] == 2 for r in reshards)  # the original gang
    adopted = [r for r in reshards
               if r["shards"] == 1 and r["attempt"] >= 1]
    assert adopted, reshards
    assert (adopted[0]["block_lo"], adopted[0]["block_hi"]) == \
        (0, adopted[0]["blocks"])  # the survivor owns the whole store
    assert not any(r.get("event") == "binning" for r in records)

    # reference: a single-rank run resumed from the SAME snapshot the
    # shrunken survivor resumed from (the iteration-2 capture survives
    # rotation: 2/4/6 are exactly keep_last_k=3)
    snap2 = tmp_path / "shrink" / "snaps" / "snapshot.iter00000002.ckpt"
    assert snap2.exists()
    refsnaps = tmp_path / "refsnaps"
    refsnaps.mkdir()
    shutil.copy(snap2, refsnaps / snap2.name)
    ref_rc, ref_out = _run_single(
        tmp_path, "ref1", ("snapshot_freq=2", f"snapshot_dir={refsnaps}"))
    assert ref_rc == 0, ref_out[-2000:]
    assert "Resuming from checkpoint" in ref_out
    assert (tmp_path / "ref1" / "model.txt").read_text() == model


@pytest.mark.slow
def test_gang_same_topology_restart_byte_identity(tmp_path):
    """Both ranks supervised: the killed rank's supervisor restarts it,
    the barrier sees BOTH ranks, ownership re-derives unchanged, and
    the restarted gang's final model is byte-identical to an
    uninterrupted 2-rank gang run — with the shared store built exactly
    once across every incarnation."""
    _write_gang_data(tmp_path / "tr.csv")
    ref = _gang(tmp_path, "ref2", ["lightgbm_tpu"] * 2, [None, None],
                KNOBS)
    for rank, (rc, out) in enumerate(ref):
        assert rc == 0, f"ref rank {rank} failed:\n{out[-3000:]}"
    sup = _gang(tmp_path, "crash2", ["lightgbm_tpu.supervisor"] * 2,
                ["rank_crash_at_iteration=1:3"] * 2, KNOBS)
    for rank, (rc, out) in enumerate(sup):
        assert rc == 0, f"supervisor rank {rank} failed:\n{out[-3000:]}"
    out0 = sup[0][1]
    assert "supervisor: restarting rank 0 as rank 0 of 2" in out0
    assert "Resuming from checkpoint" in out0
    assert (tmp_path / "crash2" / "model.txt").read_text() == \
        (tmp_path / "ref2" / "model.txt").read_text()
    assert _manifest_build_count(tmp_path) == 1
