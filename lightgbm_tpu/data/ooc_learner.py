"""Out-of-core tree learner: leaf-wise builds over a streamed block store.

The serial learner (models/tree_learner.py) pins the whole (F, N_pad)
bin matrix on device and grows each tree inside one jitted program. At
datasets past host RAM that matrix is exactly the term that cannot
exist, so this learner inverts the layout: per-row STATISTICS
(gradients/hessians/in-bag, the row->leaf partition, scores) stay
resident at O(N * a-few-bytes), while the bin matrix streams from the
block store (data/block_store.py) through the double-buffered
prefetcher (data/prefetch.py) once per histogram request — Ou's
out-of-core boosting layout (arXiv:2005.09148), with the packed-bin
width (arXiv:1806.11248) keeping each streamed pass at 1-2 bytes per
cell.

Bitwise-parity contract: every histogram is accumulated by folding
blocks through ops/histogram.py hist_pair_fold_block — the SAME chunked
f32 Kahan-pair arithmetic as build_histograms_pair, with block
boundaries aligned to the chunk grid — so each leaf histogram, each
find_best_split call, and therefore every tree is BIT-IDENTICAL to
in-RAM training with the masked histogram engine (the serial learner at
hist_compaction=false; the frontier root/children passes are already
bitwise-equal to the masked kernel, docs/Histogram-Engine.md). The
host-side split loop below mirrors build_tree_device line for line:
same smaller-child selection, same cached-parent f32 subtraction, same
candidate bookkeeping — elementwise f32 IEEE arithmetic agrees between
numpy and XLA, and the reductions (root sums, split scan) run through
the same jitted jax functions. tests/test_out_of_core.py pins model
strings and predictions against the in-RAM reference.

Composes with bagging/GOSS (their in-bag weights arrive through the
same `inbag` vector), multiclass (per-class builds), and the PR-2
checkpoint cadence (the feature sampler is the learner's only host RNG,
captured by GBDT._rng_registry, so crash/resume stays byte-identical).
The fused multi-iteration scan is intentionally ineligible here —
per-iteration host control is what lets the bin matrix stay on disk.
"""

import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.histogram import hist_pair_fold_block, hist_pair_fold_collapse
from ..ops.split import K_MIN_SCORE, SplitParams, find_best_split
from ..parallel.heartbeat import collective_guard
from ..utils.log import Log
from .prefetch import BlockPrefetcher

F32 = np.float32
NEG_INF = np.float32(K_MIN_SCORE)


class _OwnedBlockChunks:
    """Re-iterable (lo, hi, bins, base) view over a learner's owned
    blocks for the linear leaf fit: one transient read_block memmap per
    block, rows in LOCAL coordinates (the first owned block starts at
    0, matching the learner's row_leaf/gradient layout). Iterating
    twice re-reads the blocks — the fit's two passes each stream the
    store once, keeping the resident bound unchanged."""

    def __init__(self, learner):
        self._learner = learner

    def __iter__(self):
        lrn = self._learner
        store = lrn.train_set.block_store
        lo = 0
        for b in range(lrn._blk_lo, lrn._blk_hi):
            rows = store.block_rows_of(b)
            yield lo, lo + rows, store.read_block(b), lo
            lo += rows


class OutOfCoreTreeLearner:
    """Serial-learner-compatible driver whose bin matrix never resides
    in memory. Shares the serial learner's public surface
    (init/train_device/train/_to_host_tree/_sample_features/reset_config
    + the feature-sampling RNG the checkpoint system captures)."""

    name = "out_of_core"
    partitioned_capable = False

    def __init__(self, config):
        from ..config import setup_compilation_cache
        from ..utils.random import Random
        self.config = config
        self.random = Random(config.feature_fraction_seed)
        self.train_set = None
        self.metrics = None           # bound by GBDT.reset_training_data
        setup_compilation_cache(config)

    # ------------------------------------------------------------------ init
    def init(self, train_set):
        store = getattr(train_set, "block_store", None)
        if store is None:
            Log.fatal("out_of_core=true needs a block-store dataset; "
                      "the training data was constructed in-RAM "
                      "(is the dataset a valid set or a subset?)")
        cfg = self.config
        self.train_set = train_set
        self.num_features = train_set.num_features
        self.num_data = train_set.num_data
        self.max_bin = int(train_set.max_stored_bin)
        if store.num_stored != self.num_features:
            Log.fatal("block store holds %d stored features but the "
                      "dataset maps %d", store.num_stored,
                      self.num_features)

        # contiguous owned block range over the (possibly shared)
        # store: everything on the gang learner (data/ooc_parallel.py),
        # re-derived at every init — an elastic restart that changed
        # the world re-shards ownership here, never re-bins
        blo, bhi = self._owned_block_range(store)
        self._blk_lo, self._blk_hi = int(blo), int(bhi)
        self._restart_attempt = int(
            os.environ.get("LIGHTGBM_TPU_RESTART_ATTEMPT", "0") or 0)
        self._reshard_journaled = False
        if self._restart_attempt > 0:
            # resume over a store that sat on disk through a kill, with
            # ownership this rank may have just adopted: re-check the
            # manifest crc32 of every block it NOW owns before first
            # use (BlockStoreError names any rotted block)
            store.reverify(self._blk_lo, self._blk_hi)
            Log.info("restart attempt %d: re-verified owned blocks "
                     "[%d, %d) of %s", self._restart_attempt,
                     self._blk_lo, self._blk_hi, store.directory)

        # row geometry: mirror the serial masked builder's CPU padding
        # (rows padded to the scan chunk) so the blockwise Kahan fold
        # walks the IDENTICAL chunk sequence — the parity contract.
        # Rows are LOCAL (the owned blocks'); the gang dataset view
        # already slices metadata/num_data to match.
        chunk = int(cfg.device_row_chunk)
        n = self.num_data
        owned_rows = sum(store.block_rows_of(i)
                         for i in range(self._blk_lo, self._blk_hi))
        if owned_rows != n:
            Log.fatal("owned blocks [%d, %d) hold %d rows but the "
                      "dataset view claims %d — stale ownership",
                      self._blk_lo, self._blk_hi, owned_rows, n)
        n_pad = ((n + chunk - 1) // chunk) * chunk if n > chunk else n
        self.n_pad = n_pad
        self.row_chunk = min(chunk, n_pad) if n_pad else chunk
        self.f_pad = self.num_features
        n_spans = max(1, -(-n_pad // store.block_rows))
        if n_spans > 1 and store.block_rows % self.row_chunk != 0:
            Log.fatal("block_rows=%d must be a multiple of "
                      "device_row_chunk=%d so block boundaries land on "
                      "the histogram chunk grid", store.block_rows,
                      self.row_chunk)
        spans = []
        for i in range(n_spans):
            s = i * store.block_rows
            e = min(s + store.block_rows, n_pad)
            gb = self._blk_lo + i
            data_rows = store.block_rows_of(gb) if gb < self._blk_hi \
                else 0
            spans.append((gb if data_rows else None, e - s, data_rows))
        self._spans = spans
        self._prefetcher = BlockPrefetcher(
            store, spans, depth=int(cfg.prefetch_depth),
            cache_blocks=int(cfg.block_cache_blocks))
        self._stats_prev = self._prefetcher.stats()
        self._journal_prev = self._stats_prev

        # split-scan tables (identical to the serial learner's)
        self._num_bin_pf = jnp.asarray(train_set.num_bin_array())
        self._is_cat_dev = jnp.asarray(train_set.feature_is_categorical())
        self._is_cat_host = np.asarray(train_set.feature_is_categorical())
        table = np.zeros((self.num_features, self.max_bin), dtype=np.float64)
        for i, m in enumerate(train_set.bin_mappers):
            vals = (m.bin_upper_bound if m.bin_type != 1
                    else m.bin_2_categorical.astype(np.float64))
            table[i, :len(vals)] = vals
        self._bin_value_table = table
        self._decision_type_host = np.asarray(
            [1 if m.bin_type == 1 else 0 for m in train_set.bin_mappers],
            dtype=np.int8)
        self.params = SplitParams(
            min_data_in_leaf=float(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
            lambda_l1=float(cfg.lambda_l1),
            lambda_l2=float(cfg.lambda_l2),
            min_gain_to_split=float(cfg.min_gain_to_split),
        )
        self._cache_ok = self._cache_hists(cfg)
        self._fold = self._make_fold()
        self._eval = self._make_eval()
        self._root_sums = jax.jit(lambda h: (jnp.sum(h[0, :, 0]),
                                             jnp.sum(h[0, :, 1]),
                                             jnp.sum(h[0, :, 2])))
        Log.info("Number of data: %d, number of features: %d "
                 "(out-of-core: %d blocks x %d rows, %s resident "
                 "budget %.1f MB)", self.num_data, self.num_features,
                 store.num_blocks, store.block_rows, store.dtype.name,
                 self._prefetcher.resident_bytes() / 1e6)

    def _owned_block_range(self, store):
        """(lo, hi) block range this learner streams and partitions.
        Serial: the whole store. The gang learner overrides with its
        rank's contiguous owned range (parallel/machines.py
        partition_blocks via MeshTopology.owned_block_range)."""
        return 0, store.num_blocks

    def _cache_hists(self, cfg):
        """Cache-vs-recompute through the SAME rule as the in-RAM
        masked engine (models/tree_learner.py cache_hists_fits) — the
        decision changes the f32 histogram arithmetic, so a drifted
        copy would silently break the bit-parity contract. The block
        store never bundles, so stored features == num_features."""
        from ..models.tree_learner import cache_hists_fits
        return cache_hists_fits(cfg, self.num_features, self.max_bin)

    def _make_fold(self):
        b, chunk = self.max_bin, self.row_chunk

        @jax.jit
        def fold(acc, comp, bins_blk, ghc_blk, rl_blk, leaf_id):
            # identical to masked_histograms_xla: leaf mask folded into
            # the stats, then the chunked Kahan pair — continued across
            # block boundaries by the carry
            mask = (rl_blk == leaf_id).astype(jnp.float32)
            ghc = (ghc_blk * mask[None, :]).T
            return hist_pair_fold_block(acc, comp, bins_blk, ghc, b,
                                        row_chunk=chunk)

        return fold

    def _make_eval(self):
        params = self.params  # compile-time constants, as in-RAM

        @jax.jit
        def ev(hist, sum_g, sum_h, cnt, fmask, num_bin_pf, is_cat):
            return find_best_split(hist, sum_g, sum_h, cnt, num_bin_pf,
                                   is_cat, fmask, params)

        return ev

    # ------------------------------------------------------- serial surface
    def reset_config(self, config):
        self.config = config
        if self.train_set is not None:
            self.init(self.train_set)

    def _sample_features(self):
        cfg = self.config
        if cfg.feature_fraction >= 1.0:
            return np.ones(self.num_features, dtype=bool)
        used_cnt = int(self.num_features * cfg.feature_fraction)
        return self.random.sample_mask(self.num_features, max(used_cnt, 1))

    def local_row_leaf(self, out, n_local):
        return out["row_leaf"][:n_local]

    def score_layout(self):
        """None: the bins stream from disk a block at a time, so there
        is no fused scan over them (models/gbdt.py _fused_learner_ok)."""
        return None

    def local_leaf_values(self, out):
        return out["leaf_value"]

    def linear_fit_context(self):
        """(chunks, bin_value_table, fit_chunk) for the linear leaf fit
        (models/linear_leaves.py): a re-iterable that streams the owned
        blocks in ascending local-row order. Block boundaries land on
        the device_row_chunk grid (enforced at init), so the fit's f64
        accumulation walks the IDENTICAL chunk sequence as the resident
        serial learner — the same parity contract as the histogram
        fold."""
        return (_OwnedBlockChunks(self), self.train_set.bin_value_table(),
                int(self.config.device_row_chunk))

    # --------------------------------------------------------------- builds
    def _leaf_hist(self, leaf_id, ghc_dev, rl_dev):
        """One streamed pass: every block folds into the Kahan carry in
        row order. Returns the collapsed (F, B, 3) histogram (device,
        synced — the caller consumes it on host immediately). The pass
        wall (IO + folds + sync) feeds the prefetcher's overlap metric;
        its queue-wait counter is the stall numerator."""
        f, b = self.num_features, self.max_bin
        acc = jnp.zeros((f, b, 3), jnp.float32)
        comp = jnp.zeros((f, b, 3), jnp.float32)
        lid = jnp.int32(leaf_id)
        t0 = time.perf_counter()
        for s, e, blk in self._prefetcher.stream():
            acc, comp = self._fold(acc, comp, blk, ghc_dev[:, s:e],
                                   rl_dev[s:e], lid)
        # serial: collapse the local pair; gang: exchange partial
        # pairs across ranks first (data/ooc_parallel.py) — either
        # way the pass wall includes the sync, so overlap_pct keeps
        # meaning 'share of the pass NOT stalled on IO'
        hist = self._combine_pair(acc, comp)
        self._prefetcher.note_pass_wall(time.perf_counter() - t0)
        return hist

    def _combine_pair(self, acc, comp):
        """Local (acc, comp) Kahan pair -> final (F, B, 3) histogram.
        The collapse wait is a blocking device sync: arm the watchdog +
        wait attribution around it like every other sync point (the
        guard is a no-op when disarmed/unbound)."""
        with collective_guard("ooc:hist_fold"):
            return jax.block_until_ready(
                hist_pair_fold_collapse(acc, comp))

    def _partition_update(self, rl, best_leaf, right_id, feat, thr, cat):
        """DataPartition::Split, blockwise: the split feature's bin
        column streams one contiguous ~rows-byte slice per block; pad
        rows behave as bin 0 (the in-RAM builder's zero-padded
        columns)."""
        store = self.train_set.block_store
        n = self.num_data
        for i in range(self._blk_lo, self._blk_hi):
            s = (i - self._blk_lo) * store.block_rows
            e = s + store.block_rows_of(i)
            col = store.feature_rows(i, feat).astype(np.int64)
            seg = rl[s:e]
            go_left = (col == thr) if cat else (col <= thr)
            seg[(seg == best_leaf) & ~go_left] = right_id
        if self.n_pad > n:
            pad = rl[n:]
            go_left0 = (0 == thr) if cat else (0 <= thr)
            if not go_left0:
                pad[pad == best_leaf] = right_id

    def _eval_split(self, hist, sum_g, sum_h, cnt, fmask):
        out = self._eval(hist, F32(sum_g), F32(sum_h), F32(cnt), fmask,
                         self._num_bin_pf, self._is_cat_dev)
        with collective_guard("ooc:split_eval"):
            return jax.device_get(out)

    def train_device(self, grad, hess, inbag=None):
        """Grow one tree, streaming the bin matrix per histogram pass.
        Returns the builder-output dict (host numpy arrays; the GBDT
        layer consumes it exactly like the serial learner's device
        dict)."""
        n, n_pad = self.num_data, self.n_pad
        g = np.asarray(grad, dtype=F32)
        h = np.asarray(hess, dtype=F32)
        ib = (np.ones(n, dtype=F32) if inbag is None
              else np.asarray(inbag, dtype=F32)[:n])
        pad = n_pad - n
        if pad:
            g = np.concatenate([g, np.zeros(pad, F32)])
            h = np.concatenate([h, np.zeros(pad, F32)])
            ib = np.concatenate([ib, np.zeros(pad, F32)])
        # same elementwise f32 products as the in-graph builder's
        # g_in = grad * inbag / h_in = hess * inbag
        ghc_t = np.stack([g * ib, h * ib, ib])
        fmask = self._sample_features()
        out = self._grow_tree(jnp.asarray(ghc_t), fmask)
        self._account_telemetry()
        return out

    def train(self, grad, hess, inbag=None):
        out = self.train_device(grad, hess, inbag)
        tree = self._to_host_tree(out)
        return tree, out["row_leaf"][:self.num_data], out["leaf_value"]

    def _grow_tree(self, ghc_dev, fmask):
        """Host mirror of build_tree_device's leaf-wise loop (same
        bookkeeping, same f32 arithmetic, histograms streamed)."""
        cfg = self.config
        l = int(cfg.num_leaves)
        max_depth = int(cfg.max_depth)
        n_pad = self.n_pad
        f, b = self.num_features, self.max_bin

        rl = np.zeros(n_pad, dtype=np.int32)
        rl_dev = jnp.asarray(rl)
        hist_root = self._leaf_hist(0, ghc_dev, rl_dev)
        with collective_guard("ooc:root_sums"):
            root_g, root_h, root_c = jax.device_get(
                self._root_sums(hist_root))
        root_split = self._eval_split(hist_root, root_g, root_h, root_c,
                                      fmask)

        st = {
            "best_gain": np.full(l, NEG_INF, dtype=F32),
            "best_feature": np.zeros(l, np.int32),
            "best_threshold": np.zeros(l, np.int32),
            "best_lg": np.zeros(l, F32), "best_lh": np.zeros(l, F32),
            "best_lc": np.zeros(l, F32), "best_rg": np.zeros(l, F32),
            "best_rh": np.zeros(l, F32), "best_rc": np.zeros(l, F32),
            "best_lout": np.zeros(l, F32), "best_rout": np.zeros(l, F32),
            "leaf_depth": np.zeros(l, np.int32),
            "split_feature": np.zeros(l - 1, np.int32),
            "split_threshold_bin": np.zeros(l - 1, np.int32),
            "split_gain": np.zeros(l - 1, F32),
            "left_child": np.zeros(l - 1, np.int32),
            "right_child": np.zeros(l - 1, np.int32),
            "leaf_parent": np.full(l, -1, np.int32),
            "leaf_value": np.zeros(l, F32),
            "leaf_count": np.zeros(l, np.int32),
            "internal_value": np.zeros(l - 1, F32),
            "internal_count": np.zeros(l - 1, np.int32),
        }
        st["leaf_count"][0] = np.int32(root_c)
        self._write_candidate(st, 0, root_split, F32(root_split.gain))

        cache = (np.zeros((l, f, b, 3), F32) if self._cache_ok else None)
        if cache is not None:
            cache[0] = np.asarray(hist_root)

        n_splits = 0
        for i in range(l - 1):
            best_leaf = int(np.argmax(st["best_gain"]))
            gain = st["best_gain"][best_leaf]
            if not gain > 0.0:
                break
            node, right_id = i, i + 1
            feat = int(st["best_feature"][best_leaf])
            thr = int(st["best_threshold"][best_leaf])

            # ---- tree bookkeeping (apply_tree_split, mirrored)
            parent = int(st["leaf_parent"][best_leaf])
            if parent >= 0:
                if st["left_child"][parent] == ~best_leaf:
                    st["left_child"][parent] = node
                else:
                    st["right_child"][parent] = node
            st["left_child"][node] = ~best_leaf
            st["right_child"][node] = ~right_id
            st["split_feature"][node] = feat
            st["split_threshold_bin"][node] = thr
            st["split_gain"][node] = gain
            st["internal_value"][node] = st["leaf_value"][best_leaf]
            st["internal_count"][node] = np.int32(
                F32(st["best_lc"][best_leaf] + st["best_rc"][best_leaf]))
            st["leaf_parent"][best_leaf] = node
            st["leaf_parent"][right_id] = node
            st["leaf_value"][best_leaf] = st["best_lout"][best_leaf]
            st["leaf_value"][right_id] = st["best_rout"][best_leaf]
            st["leaf_count"][best_leaf] = np.int32(st["best_lc"][best_leaf])
            st["leaf_count"][right_id] = np.int32(st["best_rc"][best_leaf])
            n_splits += 1

            # ---- partition update (blockwise column stream)
            cat = bool(self._is_cat_host[feat])
            self._partition_update(rl, best_leaf, right_id, feat, thr, cat)
            rl_dev = jnp.asarray(rl)

            # ---- child histograms: smaller child streamed, larger by
            # cached-parent subtraction (same f32 sub as the device path)
            left_is_small = bool(st["best_lc"][best_leaf]
                                 <= st["best_rc"][best_leaf])
            small = best_leaf if left_is_small else right_id
            hist_small = np.asarray(self._leaf_hist(small, ghc_dev, rl_dev))
            if cache is not None:
                hist_large = cache[best_leaf] - hist_small
                hist_left = hist_small if left_is_small else hist_large
                hist_right = hist_large if left_is_small else hist_small
                cache[best_leaf] = hist_left
                cache[right_id] = hist_right
            else:
                hist_left = (hist_small if small == best_leaf else
                             np.asarray(self._leaf_hist(best_leaf, ghc_dev,
                                                        rl_dev)))
                hist_right = (hist_small if small == right_id else
                              np.asarray(self._leaf_hist(right_id, ghc_dev,
                                                         rl_dev)))

            # ---- children leaf state + depth guard
            child_depth = int(st["leaf_depth"][best_leaf]) + 1
            st["leaf_depth"][best_leaf] = child_depth
            st["leaf_depth"][right_id] = child_depth
            lsplit = self._eval_split(hist_left, st["best_lg"][best_leaf],
                                      st["best_lh"][best_leaf],
                                      st["best_lc"][best_leaf], fmask)
            rsplit = self._eval_split(hist_right, st["best_rg"][best_leaf],
                                      st["best_rh"][best_leaf],
                                      st["best_rc"][best_leaf], fmask)
            depth_ok = max_depth < 0 or child_depth < max_depth
            lgain = F32(lsplit.gain) if depth_ok else NEG_INF
            rgain = F32(rsplit.gain) if depth_ok else NEG_INF
            self._write_candidate(st, best_leaf, lsplit, lgain)
            self._write_candidate(st, right_id, rsplit, rgain)

        return {
            "n_splits": np.int32(n_splits),
            "row_leaf": rl,
            "split_feature": st["split_feature"],
            "split_threshold_bin": st["split_threshold_bin"],
            "split_gain": st["split_gain"],
            "left_child": st["left_child"],
            "right_child": st["right_child"],
            "leaf_parent": st["leaf_parent"],
            "leaf_value": st["leaf_value"],
            "leaf_count": st["leaf_count"],
            "internal_value": st["internal_value"],
            "internal_count": st["internal_count"],
        }

    @staticmethod
    def _write_candidate(st, leaf_id, sp, gain_v):
        st["best_gain"][leaf_id] = gain_v
        st["best_feature"][leaf_id] = np.int32(sp.feature)
        st["best_threshold"][leaf_id] = np.int32(sp.threshold)
        st["best_lg"][leaf_id] = F32(sp.left_sum_gradient)
        st["best_lh"][leaf_id] = F32(sp.left_sum_hessian)
        st["best_lc"][leaf_id] = F32(sp.left_count)
        st["best_rg"][leaf_id] = F32(sp.right_sum_gradient)
        st["best_rh"][leaf_id] = F32(sp.right_sum_hessian)
        st["best_rc"][leaf_id] = F32(sp.right_count)
        st["best_lout"][leaf_id] = F32(sp.left_output)
        st["best_rout"][leaf_id] = F32(sp.right_output)

    # ------------------------------------------------------ tree conversion
    def _to_host_tree(self, out, shrink=1.0):
        with collective_guard("tree_host_fetch"):
            host = jax.device_get({k: v for k, v in out.items()
                                   if k != "row_leaf"})
        return self.host_out_to_tree(host, shrink)

    def host_out_to_tree(self, host, shrink=1.0):
        # identical conversion to the serial learner's (shared tables)
        from ..models.tree_learner import SerialTreeLearner
        return SerialTreeLearner.host_out_to_tree(self, host, shrink)

    # ------------------------------------------------------------ telemetry
    def _account_telemetry(self):
        """Per-train_device deltas of the prefetch counters into the
        booster's MetricsRegistry."""
        stats = self._prefetcher.stats()
        prev, self._stats_prev = self._stats_prev, stats
        d_wait = stats["prefetch_wait_s"] - prev["prefetch_wait_s"]
        d_bytes = stats["prefetch_bytes"] - prev["prefetch_bytes"]
        if self.metrics is not None:
            self.metrics.inc("transfer_bytes", int(d_bytes))
            self.metrics.observe("prefetch_wait_s", d_wait)
            self.metrics.set("prefetch_depth", self._prefetcher.depth)
            self.metrics.set("prefetch_overlap_pct",
                             stats["prefetch_overlap_pct"])

    def _gang_shape(self):
        """(world, rank) of this incarnation — (1, 0) for the serial
        learner; the gang learner overrides."""
        return 1, 0

    def _journal_reshard_once(self):
        """One `block_reshard` record per learner incarnation: this
        rank's owned block range, re-derived from the CURRENT world.
        Lazy (like the meshed learners' `mesh` record) because the
        journal opens after learner init. Across an elastic restart
        the record's shards/block range change while zero `binning`
        events appear between — the journal-side proof that survivors
        adopted blocks instead of re-binning."""
        if self._reshard_journaled:
            return
        from ..telemetry import journal as run_journal
        j = run_journal.current()
        if j is None:
            return
        self._reshard_journaled = True
        world, rank = self._gang_shape()
        j.event("block_reshard",
                blocks=int(self.train_set.block_store.num_blocks),
                shards=int(world), rank=int(rank),
                block_lo=int(self._blk_lo), block_hi=int(self._blk_hi),
                rows=int(self.num_data),
                attempt=int(self._restart_attempt), learner=self.name)

    def journal_fields(self):
        """Extra fields for the booster's per-iteration journal record
        (models/gbdt.py train_one_iter). Deltas are taken against the
        LAST journal record, not the last train_device call — a
        multiclass iteration runs K per-class builds and the one record
        must cover all of them."""
        self._journal_reshard_once()
        stats = self._prefetcher.stats()
        prev, self._journal_prev = self._journal_prev, stats
        return {
            "prefetch_wait_s": round(
                stats["prefetch_wait_s"] - prev["prefetch_wait_s"], 6),
            "prefetch_bytes": int(
                stats["prefetch_bytes"] - prev["prefetch_bytes"]),
            "prefetch_overlap_pct": stats["prefetch_overlap_pct"],
        }
