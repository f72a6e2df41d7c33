"""Parallel tree learners over a jax.sharding.Mesh.

Reference: src/treelearner/parallel_tree_learner.h and the three
implementations (feature_parallel_tree_learner.cpp,
data_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp).
The reference's hand-written collectives (Bruck allgather +
recursive-halving reduce-scatter over TCP/MPI, src/network/) are
replaced by XLA collectives over ICI/DCN, injected through ONE shared
mesh/communication layer (parallel/mesh.py) that owns the topology,
the exchange algorithms, the `comm_precision` compression, and the
per-collective wire-byte ledger.

All three learners reuse the SAME jitted tree builder
(models/tree_learner.py) under `shard_map`, with collectives at
exactly the reference's sync points:

- **Data parallel** (data_parallel_tree_learner.cpp): rows sharded.
  Default exchange is the reference's REDUCE-SCATTER design (:155-157):
  each rank reduce-scatters the smaller child's histogram pair so it
  reduces (fixed-order Kahan) and split-searches only its OWNED feature
  block, and the global best is an allgather+argmax of one tiny
  SplitInfo per rank (:58-64 global counts ride in the SplitInfo). The
  parent−sibling subtraction happens per rank on the owned block of
  the reduced histogram cache — the cross-rank subtraction trick: only
  the smaller child is ever exchanged. `hist_exchange=allgather`
  restores the full-histogram pair allgather (every rank reduces and
  searches everything).

- **Feature parallel** (feature_parallel_tree_learner.cpp): features
  sharded, all rows on every device. Each shard evaluates splits on its
  own features and the global best is an all_gather + argmax of one
  SplitInfo per shard (the 2×SplitInfo Allreduce-max, :64-72). The
  split column is broadcast from its owner with a psum (the reference
  needs no broadcast only because every rank stores ALL features;
  we shard storage too).

- **Voting parallel** (PV-Tree, voting_parallel_tree_learner.cpp): rows
  sharded, histograms kept LOCAL (hist_psum = identity); the evaluate
  hook votes on local top-k gains, all_gathers the candidate ids, and
  only the winning <=2k features' histograms are psum'd — the analog of
  the selective ReduceScatter (:226-293) — through the comm layer, so
  `comm_precision` compression and byte accounting apply there too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.tree_learner import (SerialTreeLearner, build_tree_device,
                                   chunk_pad, shape_bucketing)
from ..ops.split import (K_MIN_SCORE, find_best_split, per_feature_best,
                         split_info_at)
from ..utils.log import Log
from .heartbeat import collective_guard
# the mesh/topology/communication layer (one shim + one byte model for
# every mesh user); AXIS/shard_map/make_mesh/pair_allreduce re-exported
# here for existing import paths
from .mesh import (AXIS, COLLECTIVE_KINDS, CommPlan,  # noqa: F401
                   MeshTopology, allgather_recv_bytes, alltoall_recv_bytes,
                   compressed_allreduce, compressed_psum,
                   compressed_reduce_scatter, make_mesh, pair_allreduce,
                   pair_reduce_scatter, psum_recv_bytes,
                   resolve_hist_exchange, shard_map)

_TREE_OUT_KEYS = (
    "n_splits", "row_leaf", "split_feature", "split_threshold_bin",
    "split_gain", "left_child", "right_child", "leaf_parent", "leaf_value",
    "leaf_count", "internal_value", "internal_count",
)

_SPLIT_INFO_BYTES = 11 * 4   # SplitInfo: 11 scalar fields on the wire


def row_shard_layout(config, n):
    """(devices, rows a device) of the data-parallel learner on the mesh
    of `config` for a dataset of n rows, where it pads each shard to the
    canonical HIST_CHUNK grid (on a TPU, and under the leaf-contiguous
    builder): device i holds rows [i * S, (i + 1) * S), zeros past row
    n - 1. The dataset bins each block on its device to this layout
    (io/dataset.py _bin_dense_on_shards); None off a multi-device mesh
    of one process."""
    if jax.process_count() > 1:
        return None
    devices = list(make_mesh(config).devices.flat)
    if len(devices) < 2:
        return None
    return devices, chunk_pad(-(-n // len(devices)), shape_bucketing(config))


@functools.partial(jax.jit, static_argnums=(1, 2), static_argnames="out_sharding")
def _pack_resident(bins, f_pad, pack, out_sharding=None):
    """(F, N) device bins -> zero rows up to f_pad, and the packed words
    (4 features an int32, ops/ordered_hist.py pack_feature_words) where
    `pack`; row-wise, so each shard stays on its device."""
    f, n = bins.shape
    rows = 4 * -(-f_pad // 4) if pack else f_pad
    bins = jnp.pad(bins, ((0, rows - f), (0, 0)))
    if pack:
        p = bins.reshape(rows // 4, 4, n).astype(jnp.uint32)
        bins = jax.lax.bitcast_convert_type(
            p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16) | (p[:, 3] << 24),
            jnp.int32)
    return jax.lax.with_sharding_constraint(bins, out_sharding)


class _MeshedTreeLearner(SerialTreeLearner):
    """Common mesh plumbing: pad/shard inputs, same host-side driver.

    Multi-host: the mesh spans all global devices; each process holds
    only its row block of a row-sharded dataset (dataset_loader.cpp's
    per-rank distribution) and global arrays are assembled from the
    local blocks (parallel/distributed.py). Everything below the
    placement layer — the builder, the collectives, the hooks — is
    identical between 1 and N hosts."""

    # which input axes are sharded: "rows" or "features"
    shard_rows = True
    shard_features = False
    # the row-sharded learners re-enable the leaf-contiguous builder
    # (per-shard layouts + collectives at the evaluation points)
    partitioned_capable = False

    def _partitioned_enabled(self, cfg):
        # Row-sharded learners follow the serial "auto" rule (TPU ->
        # leaf-contiguous builder): the north-star data-parallel config
        # must hit the fast core with no flag. The reference's EXACT
        # serial == parallel tree guarantee remains available under
        # partitioned_build=false (masked + Kahan pair exchange); the
        # partitioned parity serial==parallel is pinned to f32
        # summation-order ulps by test_parallel.py.
        return super()._partitioned_enabled(cfg)

    def _compaction_enabled(self, cfg):
        """Row-sharded learners keep gather compaction OPT-IN on the
        masked builder: shard-local compaction regroups the within-chunk
        f32 partial sums (chunk boundaries no longer align with the
        serial learner's), demoting the masked path's chunk-aligned
        serial == parallel histogram agreement (a few f32 ulps of each
        cell's absolute mass, the Kahan-pair bound) to ~1e-6 — the
        reference-grade guarantee the masked data-parallel mode exists
        to provide. hist_compaction=true accepts that trade; learners
        with replicated rows (feature-parallel) follow the serial rule
        since every shard sums the identical compacted buffer."""
        from ..models.tree_learner import _tristate
        if (self.shard_rows
                and _tristate(getattr(cfg, "hist_compaction", "auto"),
                              "hist_compaction") == "auto"):
            return False
        return super()._compaction_enabled(cfg)

    def init(self, train_set):
        self.mesh = make_mesh(self.config)
        self.topology = MeshTopology(self.mesh, self.config)
        self.n_shards = self.mesh.devices.size
        self.n_proc = jax.process_count()
        self._comm_plan = CommPlan()
        self._journal_prev_comm = None
        self._mesh_journaled = False
        # per-rank loading records the global row count and the largest
        # per-rank block (identical pad lengths on every rank require it)
        self.global_num_data = getattr(train_set, "global_num_data", None) \
            or train_set.num_data
        self.local_rows_max = getattr(train_set, "local_rows_max", None)
        super().init(train_set)
        Log.info("%s tree learner on %d devices (%d processes)",
                 self.name, self.n_shards, self.n_proc)
        # the topology line an elastic shrink must change: ownership is
        # re-derived from the CURRENT mesh at every init, so a
        # supervisor relaunch with a smaller world re-shards features,
        # not just the machine list (test_supervisor / test_comm)
        d = self.topology.describe(self.f_pad)
        Log.info("mesh: %d shard(s) x %d process(es), f_pad=%d"
                 "%s, hist_exchange=%s, comm_precision=%s",
                 d["shards"], d["processes"], d["f_pad"],
                 f" (f_loc={d['f_loc']})" if "f_loc" in d else "",
                 d["exchange"], d["precision"])

    # SerialTreeLearner.init calls these hooks -------------------------------
    def _pad_rows(self, n, chunk):
        """LOCAL row padding: every process pads its block to the same
        length so shards divide evenly into chunks."""
        if not self.shard_rows:
            return super()._pad_rows(n, chunk)
        d_local = max(1, self.n_shards // self.n_proc)
        n_max = self.local_rows_max or -(-self.global_num_data // self.n_proc)
        n_max = max(n_max, n)  # never pad below the local row count
        shard = -(-n_max // d_local)
        if (jax.default_backend() == "tpu" or self._use_partitioned
                or self._use_compact):
            # per-SHARD padding through the same canonical grid as the
            # serial learner, computed from the rank-invariant n_max so
            # every rank lands on identical global shapes
            shard = self._chunk_pad(shard)
        else:
            # a shard holds whole chunks of the grid the serial learner
            # gives this process's rows (chunk, or all of them when they
            # fit one): every chunk's f32 partial then sums the rows
            # serial's does, and only the compensated folds differ —
            # the serial == parallel contract
            c = min(chunk, n_max)
            shard = -(-shard // c) * c
        return shard * d_local

    def _effective_chunk(self, chunk):
        if not self.shard_rows:
            return super()._effective_chunk(chunk)
        if (jax.default_backend() == "tpu" or self._use_partitioned
                or self._use_compact):
            # power-of-two divisor of the HIST_CHUNK row padding
            from ..models.tree_learner import pow2_scan_chunk
            return pow2_scan_chunk(chunk)
        # the scan chunk must divide the LOCAL shard length so the
        # (F, nchunks, chunk) reshape stays aligned with the row sharding
        d_local = max(1, self.n_shards // self.n_proc)
        return min(chunk, self.n_pad // d_local)

    def _pad_feature_count(self, f):
        if not self.shard_features:
            return super()._pad_feature_count(f)
        k = self.n_shards
        return ((f + k - 1) // k) * k

    def _row_sharded_map(self, fn):
        """The row-sharded learners' common shard_map shape: bins/words
        replicated-by-feature x row-sharded, per-row arrays row-sharded,
        per-feature arrays replicated."""
        return shard_map(
            fn, mesh=self.mesh,
            in_specs=(P(None, AXIS), P(AXIS), P(AXIS), P(AXIS),
                      P(None), P(None), P(None)),
            out_specs=self._out_specs())

    def score_layout(self):
        """None: a meshed learner's fused block is the data-parallel
        learner's alone (its override); the others keep the
        per-iteration loop."""
        return None

    def pad_rows_sharded(self, arr):
        """(..., N) -> (..., N_pad): zeros past row N - 1, the last axis
        sharded as the rows are (score_layout)."""
        arr = np.asarray(arr)
        widths = [(0, 0)] * (arr.ndim - 1) + [(0, self.n_pad - arr.shape[-1])]
        spec = P(*([None] * (arr.ndim - 1)), AXIS)
        return jax.device_put(np.pad(arr, widths),
                              NamedSharding(self.mesh, spec))

    def _bins_sharding(self):
        if self.shard_features:
            return NamedSharding(self.mesh, P(AXIS, None))
        return NamedSharding(self.mesh, P(None, AXIS))

    def _rows_sharding(self):
        if self.shard_rows:
            return NamedSharding(self.mesh, P(AXIS))
        return NamedSharding(self.mesh, P())  # replicated

    def _place_bins(self, bins):
        if self._use_partitioned:
            from ..ops.ordered_hist import pack_feature_words
            bins = pack_feature_words(bins)  # (W, N): same row sharding
        sh = self._bins_sharding()
        if self.n_proc > 1:
            from .distributed import place_global_rows, place_replicated
            if self.shard_rows:
                return place_global_rows(sh, bins)
            return place_replicated(sh, bins)
        return jax.device_put(bins, sh)

    def _resident_bins(self, train_set, n_pad):
        """The dataset's RowShards where they are laid out as this
        learner shards rows (same devices in mesh order, same padded
        length, no bundling); else None and the host bins are placed."""
        shards = getattr(train_set, "row_shards", None)
        if (shards is None or not self.shard_rows or self.n_proc > 1
                or self._bundle is not None
                or shards.devices != list(self.mesh.devices.flat)
                or shards.shard_rows * len(shards.devices) != n_pad):
            return None
        return shards.array

    def _place_resident_bins(self, bins, f_pad):
        """Device-resident (F, N_pad) row-sharded bins -> the builder's
        operand on the same devices: features padded to f_pad, packed
        into words under the leaf-contiguous builder."""
        return _pack_resident(bins, f_pad, bool(self._use_partitioned),
                              out_sharding=self._bins_sharding())

    def _place_rows(self, arr):
        sh = self._rows_sharding()
        if self.n_proc > 1:
            from .distributed import place_global_rows, place_replicated
            if self.shard_rows:
                return place_global_rows(sh, np.asarray(arr))
            return place_replicated(sh, np.asarray(arr))
        return jax.device_put(arr, sh)

    def _place_rep(self, arr):
        """Replicated small arrays (masks, per-feature tables)."""
        if self.n_proc > 1:
            from .distributed import place_replicated
            return place_replicated(NamedSharding(self.mesh, P()), arr)
        return jnp.asarray(arr)

    # The watchdog-armed device-sync points. `train_device` launches
    # the builder whose collectives block until every peer arrives —
    # with jax's async dispatch the WAIT can surface at launch, at the
    # row-leaf host gather, or at the leaf-value fetch, so all three
    # are bracketed; whichever one a dead/straggling peer wedges, the
    # watchdog names it and aborts instead of hanging forever
    # (parallel/heartbeat.py; armed only when `collective_timeout_s`
    # is set, zero overhead otherwise).
    def train_device(self, grad, hess, inbag=None):
        with collective_guard(f"{self.name}:tree_build"):
            return super().train_device(grad, hess, inbag)

    def local_row_leaf(self, out, n_local):
        """This process's slice of the global row->leaf partition (for
        the local score updater)."""
        if self.n_proc == 1 or not self.shard_rows:
            return out["row_leaf"][:n_local]
        with collective_guard(f"{self.name}:row_leaf_gather"):
            shards = sorted(out["row_leaf"].addressable_shards,
                            key=lambda s: s.index[0].start)
            # shards are committed to distinct local devices; assemble
            # on host
            local = np.concatenate(
                [np.asarray(s.data) for s in shards])[:n_local]
        self._account_transfer(local.nbytes)
        return local

    def local_leaf_values(self, out):
        """Fully-replicated global -> local array (multi-host)."""
        if self.n_proc == 1:
            return out["leaf_value"]
        with collective_guard(f"{self.name}:leaf_value_fetch"):
            host = jax.device_get(out["leaf_value"])
        self._account_transfer(np.asarray(host).nbytes)
        return jnp.asarray(host)

    def _account_transfer(self, nbytes):
        """Device->host bytes pulled at this learner's sync points,
        counted into the owning booster's metrics registry (`metrics`
        is bound by GBDT.reset_training_data; telemetry/registry.py)."""
        m = getattr(self, "metrics", None)
        if m is not None:
            m.inc("transfer_bytes", int(nbytes))

    # ------------------------------------------------ collective-byte ledger
    def account_tree_collectives(self, n_splits):
        """Advance the `collective_bytes{kind}` counters by this tree's
        realized wire bytes (mesh.py CommPlan; collective shapes are
        static, so root + per-split × n_splits is exact). Called by the
        boosting driver right after the per-tree leaf-count sync
        (models/gbdt.py train_one_iter)."""
        m = getattr(self, "metrics", None)
        if m is not None and self._comm_plan is not None:
            self._comm_plan.account(m, max(int(n_splits), 0))

    def journal_fields(self):
        """Per-iteration collective-byte deltas for the run journal
        (models/gbdt.py train_one_iter; deltas are against the LAST
        journal record so one record covers a multiclass iteration's K
        builds)."""
        self._journal_mesh_once()
        m = getattr(self, "metrics", None)
        if m is None:
            return {}
        cur = {k: int(m.counter(f"collective_bytes_{k}").value)
               for k in COLLECTIVE_KINDS}
        prev = self._journal_prev_comm or {k: 0 for k in cur}
        self._journal_prev_comm = cur
        return {"collective_bytes":
                {k: cur[k] - prev.get(k, 0) for k in cur}}

    def _journal_mesh_once(self):
        """One `mesh` record per learner incarnation: the journal-side
        proof that an elastic shrink re-sharded feature ownership (the
        record's shards/f_loc change across a restart). Lazy because
        the journal opens after learner init."""
        if self._mesh_journaled:
            return
        from ..telemetry import journal as run_journal
        j = run_journal.current()
        if j is None:
            return
        self._mesh_journaled = True
        j.event("mesh", learner=self.name,
                **self.topology.describe(self.f_pad))

    def _out_specs(self):
        specs = {k: P() for k in _TREE_OUT_KEYS}
        if self.shard_rows:
            specs["row_leaf"] = P(AXIS)
        return specs


class DataParallelTreeLearner(_MeshedTreeLearner):
    """Row-sharded learner (data_parallel_tree_learner.cpp).

    Three cores, selected like the serial learner's:

    - the partitioned (leaf-contiguous) builder — the default on TPU
      under partitioned_build=auto — where each shard keeps its own
      layout and every segment histogram is one f32 psum (through the
      comm layer: `comm_precision=bf16` compresses the wire word),
      matching the serial partitioned learner up to f32 summation-order
      ulps;
    - the masked builder's REDUCE-SCATTER exchange (the default
      elsewhere; `hist_exchange=auto|reduce_scatter`): each shard owns
      a contiguous feature block, the smaller child's Kahan pair is
      all_to_all'd in `comm_groups` feature-shard groups (group g+1's
      collective can be in flight while group g is being searched),
      folded in fixed source order — bit-identical per owned feature to
      the allgather-pair fold — and searched locally; the global best
      is an allgather+argmax of one SplitInfo per shard. Trees are
      IDENTICAL to the serial masked learner at `comm_precision=pair`;
    - the masked builder's legacy ALLGATHER exchange
      (`hist_exchange=allgather`, and bundled datasets whose stored-
      slot histograms every shard must expand): the full-histogram
      Kahan pair allgather with the same serial-parity guarantee, at
      W× the wire bytes."""
    name = "data"
    shard_rows = True
    partitioned_capable = True

    def score_layout(self):
        """(N_pad, sharding of a (K, N_pad) array) on which a fused
        block keeps the score and every other per-row array, so that
        row i sits on the chip that holds row i's bins; None where the
        rows span processes (each holds its own block)."""
        if self.n_proc > 1:
            return None
        return self.n_pad, NamedSharding(self.mesh, P(None, AXIS))

    def _rs_eligible(self):
        """Reduce-scatter runs on the masked core for unbundled
        datasets on real (>1 shard) meshes. Bundled (EFB) datasets
        exchange STORED-SLOT histograms that every shard must expand to
        its virtual features, so ownership would not partition the
        search; they keep the allgather exchange."""
        return (not self._use_partitioned and self._bundle is None
                and self.n_shards > 1
                and resolve_hist_exchange(self.config) != "allgather")

    def _pad_feature_count(self, f):
        if self._use_partitioned or not self._rs_eligible():
            return super()._pad_feature_count(f)
        # reduce-scatter: every shard owns an equal contiguous block
        k = self.n_shards
        return ((f + k - 1) // k) * k

    def _make_build_core(self, cfg, chunk):
        num_leaves = int(cfg.num_leaves)
        max_bin = self.max_bin
        params = self.params
        max_depth = int(cfg.max_depth)
        topo = self.topology
        precision = topo.comm_precision
        w = self.n_shards
        self._comm_plan = plan = CommPlan()

        if self._use_partitioned:
            from ..models.partitioned import build_tree_partitioned
            f_real = self.num_features
            psum = functools.partial(compressed_psum, axis_name=AXIS,
                                     precision=precision)
            cache_hists = self._cache_hists(cfg)
            # segment histograms are (stored, B, 3) f32 psums (bf16
            # halves the wire word); one reduction per root + per split
            seg = self.f_pad * max_bin * 3 * (2 if precision == "bf16"
                                              else 4)
            plan.add("hist_reduce", root=psum_recv_bytes(seg, w),
                     per_split=psum_recv_bytes(seg, w))

            def dp_part_fn(words, grad, hess, inbag, fmask, num_bin_pf,
                           is_cat):
                return build_tree_partitioned(
                    words, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                    num_leaves=num_leaves, max_bin=max_bin, params=params,
                    max_depth=max_depth, f_real=f_real,
                    hist_reduce_fn=psum, cache_hists=cache_hists,
                    **self._bundle_partitioned_kwargs(num_bin_pf))

            return self._row_sharded_map(dp_part_fn)

        # masked core: choose the histogram-exchange algorithm
        use_rs = self._rs_eligible()
        self._use_reduce_scatter = use_rs
        if (resolve_hist_exchange(cfg) == "reduce_scatter" and not use_rs
                and self.n_shards > 1):
            Log.warning("hist_exchange=reduce_scatter unavailable for "
                        "bundled datasets; using the allgather pair "
                        "exchange")
        hist_words = self.f_pad * max_bin * 3 * 4    # one f32 histogram

        if not use_rs:
            if precision == "pair":
                exchange_fn = pair_allreduce
                unit = 2 * allgather_recv_bytes(hist_words, w)
            else:
                exchange_fn = functools.partial(compressed_allreduce,
                                                precision=precision)
                unit = allgather_recv_bytes(
                    hist_words // (2 if precision == "bf16" else 1), w)
            plan.add("hist_reduce", root=unit, per_split=unit)

            def dp_fn(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat):
                # the allgather exchange already yields the GLOBAL
                # histogram on every shard, and root sums are derived
                # from it — so the scalar-sum hook is identity.
                # Shard-local compaction (opt-in, _compaction_enabled)
                # keeps the pair contract: each shard's compacted Kahan
                # pair feeds the same fixed-order reduction.
                return build_tree_device(
                    bins, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                    num_leaves=num_leaves, max_bin=max_bin, params=params,
                    max_depth=max_depth, row_chunk=chunk,
                    hist_psum_fn=exchange_fn,
                    compact_hist=self._use_compact,
                    **self._bundle_kwargs(bins, num_bin_pf))

            return self._row_sharded_map(dp_fn)

        # ---- reduce-scatter core -----------------------------------------
        f_loc = topo.feature_shard(self.f_pad)
        groups = topo.exchange_groups(f_loc)
        self._comm_groups_effective = groups
        if precision == "pair":
            exchange_fn = functools.partial(pair_reduce_scatter,
                                            n_shards=w, groups=groups)
            unit = 2 * alltoall_recv_bytes(hist_words, w)
        else:
            exchange_fn = functools.partial(compressed_reduce_scatter,
                                            n_shards=w, groups=groups,
                                            precision=precision)
            unit = alltoall_recv_bytes(
                hist_words // (2 if precision == "bf16" else 1), w)
        # one smaller-child exchange per split + the root build; the
        # larger child is parent − smaller on the OWNED block (the
        # cross-rank subtraction trick — never exchanged)
        plan.add("hist_reduce", root=unit, per_split=unit)
        # split search is local; the global best is one SplitInfo per
        # shard (root evaluates once, each split evaluates 2 children)
        sp_unit = allgather_recv_bytes(_SPLIT_INFO_BYTES, w)
        plan.add("split_gather", root=sp_unit, per_split=2 * sp_unit)
        # root sums broadcast from the global-feature-0 owner (3 scalars)
        plan.add("leaf_sync", root=3 * psum_recv_bytes(4, w))
        fg = f_loc // groups

        def dp_rs_fn(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat):
            shard = jax.lax.axis_index(AXIS)
            start = shard * f_loc
            nbp_loc = jax.lax.dynamic_slice_in_dim(num_bin_pf, start, f_loc)
            cat_loc = jax.lax.dynamic_slice_in_dim(is_cat, start, f_loc)
            fm_loc = jax.lax.dynamic_slice_in_dim(fmask, start, f_loc)

            def sum_bcast(s):
                # root sums must come from GLOBAL feature 0 (the serial
                # learner's convention) — shard 0 owns it; broadcast its
                # value so every shard evaluates with identical parents
                return jax.lax.psum(jnp.where(shard == 0, s, 0.0), AXIS)

            def evaluate(hist3, sum_g, sum_h, cnt):
                # hist3 is this shard's OWNED (f_loc, B, 3) block of the
                # reduce-scattered histogram. Search it per exchange
                # group: group g's gains depend only on group g's
                # collective, so the scheduler can overlap group g+1's
                # exchange with this search (mesh.py
                # _scatter_feature_groups).
                gains_parts, thr_parts = [], []
                for g in range(groups):
                    sl = slice(g * fg, (g + 1) * fg)
                    gains_g, thr_g = per_feature_best(
                        hist3[sl], sum_g, sum_h, cnt, nbp_loc[sl],
                        cat_loc[sl], fm_loc[sl], params)
                    gains_parts.append(gains_g)
                    thr_parts.append(thr_g)
                gains = jnp.concatenate(gains_parts)
                thr = jnp.concatenate(thr_parts)
                # within the shard: first max = smallest owned feature;
                # across shards: first max = smallest shard — together
                # the serial argmax tie-break, because ownership blocks
                # ascend with shard index
                best_local = jnp.argmax(gains).astype(jnp.int32)
                sp = split_info_at(hist3, sum_g, sum_h, cnt, cat_loc,
                                   params, best_local, thr[best_local],
                                   gains[best_local])
                sp = sp._replace(feature=sp.feature + start)
                gathered = jax.lax.all_gather(sp, AXIS)
                widx = jnp.argmax(gathered.gain)
                return jax.tree_util.tree_map(lambda x: x[widx], gathered)

            return build_tree_device(
                bins, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                num_leaves=num_leaves, max_bin=max_bin, params=params,
                max_depth=max_depth, row_chunk=chunk,
                hist_psum_fn=exchange_fn, sum_psum_fn=sum_bcast,
                evaluate_fn=evaluate,
                compact_hist=self._use_compact)

        return self._row_sharded_map(dp_rs_fn)


class FeatureParallelTreeLearner(_MeshedTreeLearner):
    """Feature-sharded learner (feature_parallel_tree_learner.cpp).
    All rows on every device, features split across devices; the
    reference's greedy bin-balanced feature assignment (:28-43) is
    replaced by a block partition of the feature axis."""
    name = "feature"
    shard_rows = False
    shard_features = True

    # replicate the split-column bin copy only below this size; larger
    # datasets keep the owner-broadcast psum (memory >> one allreduce
    # of (N,) int32 per split)
    REPLICATED_BINS_MAX_BYTES = 1 << 30

    def _setup_bundle_shards(self, stored):
        """Bundled (EFB) datasets under feature sharding: virtual
        features stay block-sharded in natural order (shard t owns
        [t*f_loc, (t+1)*f_loc)), and each shard is handed exactly the
        slot rows its features live in — at most f_loc distinct slots,
        so per-shard storage never exceeds the unbundled layout. Slot
        histograms expand to virtual features with per-shard LOCAL
        gather maps (the feature-sharded analog of io/bundling.py's
        expansion_maps; the reference's FP learner needs none of this
        because every machine stores all features,
        feature_parallel_tree_learner.cpp:28-43)."""
        plan = self._bundle
        k = self.n_shards
        f_loc = self.f_pad // k
        f_real = self.num_features
        mappers = self.train_set.bin_mappers
        b_stored = int(self.max_bin)
        b_virtual = int(self.train_set.max_num_bin)
        shard_slots = []
        for t in range(k):
            feats = np.arange(t * f_loc, min((t + 1) * f_loc, f_real))
            shard_slots.append(np.unique(plan.feat_slot[feats])
                               if len(feats) else np.zeros(0, np.int64))
        s_loc = max(1, max(len(s) for s in shard_slots))
        sel = np.zeros(k * s_loc, np.int64)
        pad_cell = s_loc * b_stored        # flattened index of a zero row
        src = np.full((self.f_pad, b_virtual), pad_cell, np.int32)
        slot_of = np.full(self.f_pad, s_loc, np.int32)  # pad -> zero total
        for t, slots in enumerate(shard_slots):
            sel[t * s_loc:t * s_loc + len(slots)] = slots
            local = {int(s): i for i, s in enumerate(slots)}
            for j in range(t * f_loc, min((t + 1) * f_loc, f_real)):
                li = local[int(plan.feat_slot[j])]
                slot_of[j] = li
                off = int(plan.feat_offset[j])
                nb = int(mappers[j].num_bin)
                src[j, 1:nb] = li * b_stored + off + np.arange(1, nb)
        self._fp_s_loc = s_loc
        self._fp_src = self._place_rep(src)
        self._fp_slot_of = self._place_rep(slot_of)
        return stored[sel]                 # (k * s_loc, N) stacked

    def _keep_replicated_copy(self, bins):
        # the reference stores ALL data on every machine in feature-
        # parallel mode (feature_parallel_tree_learner.cpp); when that
        # fits, keep a replicated copy for split-column reads so applying
        # a split needs no collective
        if bins.nbytes > self.REPLICATED_BINS_MAX_BYTES:
            self._bins_replicated = None
            return
        rep = NamedSharding(self.mesh, P())
        if self.n_proc > 1:
            from .distributed import place_replicated
            self._bins_replicated = place_replicated(rep, bins)
        else:
            self._bins_replicated = jax.device_put(bins, rep)

    def _place_bins(self, bins):
        if getattr(self, "_bundle", None) is not None:
            # strip the generic virtual-feature zero-pad rows appended
            # past the stored slot matrix, then stack per-shard slots
            stored = np.ascontiguousarray(bins[:self._bundle.num_slots])
            self._keep_replicated_copy(stored)
            stacked = self._setup_bundle_shards(stored)
            return super()._place_bins(stacked)
        self._keep_replicated_copy(bins)
        return super()._place_bins(bins)

    def _make_build_core(self, cfg, chunk):
        num_leaves = int(cfg.num_leaves)
        max_bin = self.max_bin
        params = self.params
        max_depth = int(cfg.max_depth)
        f_loc = self.f_pad // self.n_shards
        compact = self._use_compact
        w = self.n_shards
        self._comm_plan = plan = CommPlan()

        replicated = self._bins_replicated is not None
        bundled = getattr(self, "_bundle", None) is not None
        s_loc = self._fp_s_loc if bundled else f_loc

        # the Allreduce-max of SplitInfo: root evaluates once, every
        # split evaluates both children
        sp_unit = allgather_recv_bytes(_SPLIT_INFO_BYTES, w)
        plan.add("split_gather", root=sp_unit, per_split=2 * sp_unit)
        # root-sum broadcast (3 scalars, once per tree)
        plan.add("leaf_sync", root=3 * psum_recv_bytes(4, w))
        if not replicated:
            # owner-broadcast of the (N_pad,) int32 split column at
            # every partition update
            plan.add("leaf_sync",
                     per_split=psum_recv_bytes(self.n_pad * 4, w))

        # replicated bundle tables are closed over (same pattern as the
        # row-sharded learners' _bundle_kwargs); only the genuinely
        # PER-SHARD maps (src_loc, slot_of_loc) travel as operands
        if bundled:
            fslot_full = self._bundle_feat_slot
            nbv_full = self._num_bin_pf          # global virtual (f_pad,)
            bundle_window = self._bundle_window

        def fp_fn(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                  is_cat_full, bins_full, src_loc, slot_of_loc):
            shard = jax.lax.axis_index(AXIS)

            def sum_bcast(s):
                # root sums derive from each shard's LOCAL feature 0,
                # whose bin-sum rounding differs per shard; broadcast
                # shard 0's value so every shard evaluates splits with
                # identical parent sums (matches the serial learner,
                # which uses global feature 0)
                return jax.lax.psum(jnp.where(shard == 0, s, 0.0), AXIS)

            def evaluate(hist3, sum_g, sum_h, cnt):
                sp = find_best_split(hist3, sum_g, sum_h, cnt,
                                     num_bin_pf, is_cat, fmask, params)
                sp = sp._replace(feature=sp.feature + shard * f_loc)
                # Allreduce-max of SplitInfo (:64-72): gather one best
                # per shard, pick max gain; shards are stacked in
                # axis-index order so the first max has the smallest
                # global feature id (SplitInfo tie-break)
                gathered = jax.lax.all_gather(sp, AXIS)
                widx = jnp.argmax(gathered.gain)
                return jax.tree_util.tree_map(lambda x: x[widx], gathered)

            def expand(h):
                # local slot histogram -> this shard's virtual features
                # (per-shard maps from _setup_bundle_shards); the
                # appended zero rows serve both the unused-bin pad cell
                # and pad features' slot totals
                kk = h.shape[-1]
                flat = jnp.concatenate(
                    [h.reshape(-1, kk), jnp.zeros((1, kk), h.dtype)], axis=0)
                hv = jnp.take(flat, src_loc, axis=0)       # (f_loc, B_v, 3)
                slot_tot = jnp.concatenate(
                    [jnp.sum(h, axis=1), jnp.zeros((1, kk), h.dtype)], axis=0)
                hv0 = (jnp.take(slot_tot, slot_of_loc, axis=0)
                       - jnp.sum(hv[:, 1:, :], axis=1))
                return hv.at[:, 0, :].set(hv0)

            def split_col(feat):
                # the reference stores ALL data per machine in feature-
                # parallel mode; when the replicated copy fits (see
                # _place_bins), the split column is a direct read and
                # applying a split needs no collective. Otherwise fall
                # back to broadcasting the owner shard's column.
                if replicated and not bundled:
                    return jnp.take(bins_full, feat, axis=0).astype(jnp.int32)
                if replicated:
                    sc = jnp.take(bins_full, fslot_full[feat],
                                  axis=0).astype(jnp.int32)
                    return bundle_window(sc, feat, nbv_full)
                lo = shard * f_loc
                owned = (feat >= lo) & (feat < lo + f_loc)
                local_feat = jnp.clip(feat - lo, 0, f_loc - 1)
                if bundled:
                    lsl = jnp.clip(slot_of_loc[local_feat], 0, s_loc - 1)
                    sc = jnp.take(bins, lsl, axis=0).astype(jnp.int32)
                    col = bundle_window(sc, feat, nbv_full)
                else:
                    col = jnp.take(bins, local_feat, axis=0).astype(jnp.int32)
                return jax.lax.psum(jnp.where(owned, col, 0), AXIS)

            return build_tree_device(
                bins, grad, hess, inbag, fmask, num_bin_pf, is_cat_full,
                num_leaves=num_leaves, max_bin=max_bin, params=params,
                max_depth=max_depth, row_chunk=chunk,
                sum_psum_fn=sum_bcast,
                evaluate_fn=evaluate, split_col_fn=split_col,
                expand_fn=expand if bundled else (lambda h: h),
                compact_hist=compact)

        def wrapped7(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat):
            inner = shard_map(
                fp_fn, mesh=self.mesh,
                in_specs=(P(AXIS, None), P(None), P(None), P(None),
                          P(AXIS), P(AXIS), P(AXIS), P(None), P(None),
                          P(AXIS, None), P(AXIS)),
                out_specs=self._out_specs())
            # dummy stand-ins for paths the traced fn never reads
            bins_full = (self._bins_replicated if replicated
                         else jnp.zeros((1, 1), bins.dtype))
            if bundled:
                src_loc, slot_of_loc = self._fp_src, self._fp_slot_of
            else:
                k = self.n_shards
                src_loc = jnp.zeros((k, 1), jnp.int32)
                slot_of_loc = jnp.zeros(k, jnp.int32)
            return inner(bins, grad, hess, inbag, fmask, num_bin_pf,
                         is_cat, is_cat, bins_full, src_loc, slot_of_loc)

        return wrapped7


class VotingParallelTreeLearner(_MeshedTreeLearner):
    """PV-Tree (voting_parallel_tree_learner.cpp): rows sharded, but only
    the top-voted features' histograms are globally reduced — the
    selective reduction and the vote gathers ride the shared comm layer
    (comm_precision compression + collective_bytes accounting)."""
    name = "voting"
    shard_rows = True
    partitioned_capable = True

    def _make_build_core(self, cfg, chunk):
        num_leaves = int(cfg.num_leaves)
        max_bin = self.max_bin
        params = self.params
        max_depth = int(cfg.max_depth)
        top_k = max(int(cfg.top_k), 1)
        f = self.num_features
        top_k = min(top_k, f)
        n_shards = self.n_shards
        w = n_shards
        precision = self.topology.comm_precision
        self._comm_plan = plan = CommPlan()
        # the voting comms story: two tiny top-k gathers + ONE selective
        # psum of the <=top_k winning features per evaluation (root
        # evaluates once, each split twice); root sums once per tree
        vote_unit = 2 * allgather_recv_bytes(top_k * 4, w)
        sel = top_k * max_bin * 3 * (2 if precision == "bf16" else 4)
        sel_unit = psum_recv_bytes(sel, w)
        plan.add("split_gather", root=vote_unit, per_split=2 * vote_unit)
        plan.add("hist_reduce", root=sel_unit, per_split=2 * sel_unit)
        plan.add("leaf_sync", root=3 * psum_recv_bytes(4, w))
        # local vote constraints scaled by 1/num_machines
        # (voting_parallel_tree_learner.cpp:52-54)
        local_params = params._replace(
            min_data_in_leaf=params.min_data_in_leaf / self.n_shards,
            min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf / self.n_shards)
        psum = functools.partial(jax.lax.psum, axis_name=AXIS)
        sel_psum = functools.partial(compressed_psum, axis_name=AXIS,
                                     precision=precision)

        def make_evaluate(fmask, num_bin_pf, is_cat):
            """The vote-and-selectively-reduce split evaluation, shared
            by the masked and leaf-contiguous cores (both feed it the
            LOCAL histogram — hist_reduce stays identity)."""
            def evaluate(hist3, sum_g, sum_h, cnt):
                # local per-feature best gains from LOCAL leaf sums (the
                # reference votes on machine-local smaller_leaf_splits_,
                # :86,231; global sums are only for the final pick). Any
                # one feature's bins partition the local rows, so feature
                # 0's bin sums ARE the local leaf totals.
                local_g = jnp.sum(hist3[0, :, 0])
                local_h = jnp.sum(hist3[0, :, 1])
                local_c = jnp.sum(hist3[0, :, 2])
                gains, _ = per_feature_best(hist3, local_g, local_h, local_c,
                                            num_bin_pf, is_cat, fmask,
                                            local_params)
                top_g, local_top = jax.lax.top_k(gains, top_k)
                # GlobalVoting (:137-166): every machine's local top-k
                # candidates, re-scored by the WEIGHTED gain
                # gain * local_leaf_count / mean_leaf_count; per feature
                # keep the best; the global candidate set is the top-k
                # features by that score (lax.top_k's lowest-index tie
                # order plays ArrayArgs::MaxK's stable partial sort)
                w_gain = local_c * (n_shards / jnp.maximum(cnt, 1.0))
                top_wg = jnp.where(jnp.isfinite(top_g), top_g * w_gain,
                                   K_MIN_SCORE)
                all_top = jax.lax.all_gather(local_top, AXIS).reshape(-1)
                all_wg = jax.lax.all_gather(top_wg, AXIS).reshape(-1)
                feature_best = (jnp.full(f, K_MIN_SCORE, jnp.float32)
                                .at[all_top].max(all_wg))
                _, selected = jax.lax.top_k(feature_best, top_k)
                selected = jnp.sort(selected)
                # a feature nobody voted for must not win on its global
                # histogram (the reference never aggregates it at all)
                voted = jnp.isfinite(jnp.take(feature_best, selected))
                # selective reduction: psum ONLY the voted features'
                # histograms (the analog of the <=2k-feature ReduceScatter,
                # CopyLocalHistogram :167-230) — through the comm layer
                # so comm_precision compresses the wire word
                hist_sel = sel_psum(jnp.take(hist3, selected, axis=0))
                gains_sel, thr_sel = per_feature_best(
                    hist_sel, sum_g, sum_h, cnt,
                    jnp.take(num_bin_pf, selected),
                    jnp.take(is_cat, selected),
                    jnp.take(fmask, selected), params)
                gains_sel = jnp.where(voted, gains_sel, K_MIN_SCORE)
                best_local = jnp.argmax(gains_sel).astype(jnp.int32)
                sp = split_info_at(hist_sel, sum_g, sum_h, cnt,
                                   jnp.take(is_cat, selected), params,
                                   best_local, thr_sel[best_local],
                                   gains_sel[best_local])
                return sp._replace(feature=selected[best_local])

            return evaluate

        if self._use_partitioned:
            from ..models.partitioned import build_tree_partitioned
            f_real = self.num_features
            cache_hists = self._cache_hists(cfg)

            def voting_part_fn(words, grad, hess, inbag, fmask,
                               num_bin_pf, is_cat):
                return build_tree_partitioned(
                    words, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                    num_leaves=num_leaves, max_bin=max_bin, params=params,
                    max_depth=max_depth, f_real=f_real,
                    sum_psum_fn=psum, cache_hists=cache_hists,
                    evaluate_fn=make_evaluate(fmask, num_bin_pf, is_cat),
                    **self._bundle_partitioned_kwargs(num_bin_pf))

            return self._row_sharded_map(voting_part_fn)

        def voting_fn(bins, grad, hess, inbag, fmask, num_bin_pf, is_cat):
            return build_tree_device(
                bins, grad, hess, inbag, fmask, num_bin_pf, is_cat,
                num_leaves=num_leaves, max_bin=max_bin, params=params,
                max_depth=max_depth, row_chunk=chunk,
                sum_psum_fn=psum,
                evaluate_fn=make_evaluate(fmask, num_bin_pf, is_cat),
                compact_hist=self._use_compact,
                **self._bundle_kwargs(bins, num_bin_pf))

        return self._row_sharded_map(voting_fn)
