"""GBDT: the boosting loop.

Reference: src/boosting/gbdt.h:17-310, src/boosting/gbdt.cpp. Covers:
gradient boosting with bagging (record- and query-unit), per-class tree
training, shrinkage, out-of-bag score updates, metric output with early
stopping + model truncation, rollback, model text/JSON serialization,
load-from-string, split-count feature importance, raw/sigmoid/softmax
prediction paths, and booster merging for continued training.

Bagging note: the reference draws a sequential selection sample
(gbdt.cpp:161-169), uniform over fixed-size subsets. We draw the same
distribution IN-GRAPH with jax.random.permutation keyed on
(bagging_seed, iter // bagging_freq): bags are stateless per re-bag
window, identical between the fused scan and the per-iteration loop,
and exact-count like the reference's.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import heartbeat
from ..telemetry import disttrace
from ..telemetry import journal as run_journal
from ..telemetry.registry import MetricsRegistry
from ..telemetry.trace import PROCESS_TRACER, SpanTracer, scope
from ..utils import common, faults, guardrails
from ..utils.log import Log
from .score_updater import ScoreUpdater, leaf_lookup, lookup_form
from .tree import Tree
from .tree_learner import create_tree_learner

K_MIN_SCORE = -np.inf

# Model text-format version this reader/writer speaks. v1: constant
# leaves (implicit — no format_version line, byte-identical to every
# pre-linear release). v2: per-leaf linear coefficient blocks
# (models/linear_leaves.py, docs/Linear-Trees.md). Loading a HIGHER
# version is a hard error, never a silent partial parse.
MODEL_FORMAT_VERSION = 2


def f32_safe_thresholds(thr, dt):
    """f32 cast of f64 numeric thresholds rounded toward -inf so
    `x <= thr32` equals the f64 `x <= thr` for every f32-representable
    x (round-to-nearest could lift thr32 ABOVE thr and flip rows
    landing in between). Categorical thresholds are exact category
    ids: f32 holds ints < 2^24 exactly, and the id-vs-id equality is
    unaffected by the adjustment only applied to numeric nodes.
    Shared by the training-side device predictor and the serving-side
    CompiledPredictor (serving/compiled_model.py)."""
    thr32 = thr.astype(np.float32)
    numeric = dt != Tree.CATEGORICAL
    lifted = numeric & (thr32.astype(np.float64) > thr)
    return np.where(lifted,
                    np.nextafter(thr32, np.float32(-np.inf),
                                 dtype=np.float32),
                    thr32)


def device_traverse(xb, sf, thr, cat, lc, rc, node0, depth):
    """Lockstep device traversal of a (B, F) f32 row block through all
    stacked trees: every (row, tree) pair walks `depth` steps (leaves
    freeze as ~leaf in the child arrays) and the final (B, T) node
    states (~leaf encoded) come back. NaN: numeric compares send NaN
    right (fval <= thr is False) and categorical compares send NaN
    right too (a missing value is not a category id — reference
    default-direction semantics). Traced inside jitted callers
    (GBDT._predict_block_device, serving kernels)."""
    b = xb.shape[0]
    t_cnt = sf.shape[0]
    t_idx = jnp.arange(t_cnt)
    node_init = jnp.broadcast_to(node0[None, :], (b, t_cnt))
    xs = jnp.nan_to_num(xb)  # the int cast below needs a finite input

    def step(_, node):
        nd = jnp.maximum(node, 0)
        feat = sf[t_idx[None, :], nd]                       # (B, T)
        th = thr[t_idx[None, :], nd]
        is_c = cat[t_idx[None, :], nd]
        rows = jnp.arange(b)[:, None]
        fval = xb[rows, feat]
        fcat = xs[rows, feat]
        go_left = jnp.where(
            is_c,
            (fcat.astype(jnp.int32) == th.astype(jnp.int32))
            & ~jnp.isnan(fval),
            fval <= th)
        nxt = jnp.where(go_left, lc[t_idx[None, :], nd],
                        rc[t_idx[None, :], nd])
        return jnp.where(node < 0, node, nxt)

    return jax.lax.fori_loop(0, depth, step, node_init)


class LazyTree:
    """A Tree whose arrays still live on device.

    The training loop appends these WITHOUT pulling anything to host —
    the only per-iteration synchronization is the scalar n_splits stop
    check. Any host-side access (serialization, prediction, rollback,
    DART normalization) materializes a real Tree on first touch via the
    learner's batched single-transfer conversion.
    """

    # builder output is always constant-leaf; linear-leaf trees are
    # materialized eagerly (GBDT._fit_linear_tree), never lazy. A class
    # attribute keeps `getattr(m, "is_linear", ...)` probes from
    # forcing a materializing __getattr__ round-trip.
    is_linear = False

    def __init__(self, out, learner, shrink=1.0):
        # row_leaf is (N_pad,) and already consumed by the score updater;
        # holding it for every tree would pin O(iter * N) HBM.
        self._out = {k: v for k, v in out.items() if k != "row_leaf"}
        self._learner = learner
        self._shrink = float(shrink)
        self._tree = None

    @property
    def num_leaves(self):
        if self._tree is not None:
            return self._tree.num_leaves
        return int(self._out["n_splits"]) + 1

    def shrinkage(self, rate):
        if self._tree is not None:
            self._tree.shrinkage(rate)
        else:
            self._shrink = self._shrink * float(rate)

    def materialize(self) -> Tree:
        if self._tree is None:
            self._tree = self._learner._to_host_tree(self._out, shrink=self._shrink)
            self._out = None
        return self._tree

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.materialize(), name)


class _VersionedList(list):
    """Model list with a mutation counter: the stacked-prediction caches
    key on (slice, length, version) so length-preserving mutations
    (rollback + retrain) can never serve stale trees."""

    def __init__(self, *args):
        super().__init__(*args)
        self.version = 0

    def _bump(self):
        self.version = getattr(self, "version", 0) + 1

    def append(self, item):
        self._bump()
        super().append(item)

    def extend(self, items):
        self._bump()
        super().extend(items)

    def __delitem__(self, key):
        self._bump()
        super().__delitem__(key)

    def __setitem__(self, key, value):
        self._bump()
        super().__setitem__(key, value)

    def insert(self, index, item):
        self._bump()
        super().insert(index, item)

    def pop(self, index=-1):
        self._bump()
        return super().pop(index)

    def remove(self, item):
        self._bump()
        super().remove(item)

    def clear(self):
        self._bump()
        super().clear()

    def __iadd__(self, items):
        self._bump()
        return super().__iadd__(items)

    def sort(self, **kwargs):
        self._bump()
        super().sort(**kwargs)

    def reverse(self):
        self._bump()
        super().reverse()


class _BlockSnapshots:
    """Per-iteration score snapshots over one fused training block.

    After GBDT._run_fused_block, the block's tree arrays are still
    stacked on device. For every bound dataset, the score after
    in-block iteration t is base + cumsum(deltas)[t], where the deltas
    come from ONE vmapped bin-space traversal per chunk
    (score_updater._stacked_deltas) — so the engine can replay the
    reference's per-iteration eval/early-stop callback protocol
    (gbdt.cpp:210-349) without a single training-loop host sync.
    Chunking bounds device memory to ~CHUNK_BYTES per dataset; the
    caller walks t forward, so chunks stream.
    """

    CHUNK_BYTES = 64 << 20

    def __init__(self, gbdt, stacked, base_train, base_valids, t_eff,
                 natural_stop):
        self._gbdt = gbdt
        self._stacked = stacked
        self._t_eff = t_eff
        self._natural_stop = natural_stop
        self._scan_final_train = gbdt.train_score_updater.score
        self._states = [self._new_state(gbdt.train_score_updater,
                                        base_train)]
        for u, b in zip(gbdt.valid_score_updaters, base_valids):
            self._states.append(self._new_state(u, b))

    @staticmethod
    def _new_state(updater, base):
        return {"updater": updater, "base": base, "next": 0,
                "c0": 0, "chunk": None, "carry": None}

    def _flat_slice(self, t0, t1):
        """Stacked arrays sliced to [t0, t1) with the (iter, class) axes
        flattened to one leading tree axis."""
        k = self._gbdt.num_class
        out = {}
        for key, v in self._stacked.items():
            s = v[t0:t1]
            if k > 1:
                s = s.reshape(((t1 - t0) * k,) + tuple(s.shape[2:]))
            out[key] = s
        return out

    def _row_at(self, st, t):
        gb = self._gbdt
        k = gb.num_class
        u = st["updater"]
        if st["chunk"] is not None and t < st["c0"]:
            raise ValueError("snapshots must be walked forward")
        while st["chunk"] is None or t >= st["c0"] + st["chunk"].shape[0]:
            c0 = st["next"]
            v = u.num_data
            csz = max(1, min(self._t_eff - c0,
                             self.CHUNK_BYTES // max(1, k * v * 4)))
            deltas = u.deltas_by_stacked_device_trees(
                self._flat_slice(c0, c0 + csz), gb.shrinkage_rate)
            deltas = deltas.reshape(csz, k, v)
            carry = st["carry"] if st["carry"] is not None else st["base"]
            cum = carry[None] + jnp.cumsum(deltas, axis=0)
            st["carry"] = cum[-1]
            st["c0"], st["chunk"], st["next"] = c0, cum, c0 + csz
        return st["chunk"][t - st["c0"]]

    def drop_tail_to(self, t):
        """Early-stop break at in-block iteration t: drop every tree
        past iteration t WITHOUT score adjustment (the caller has set
        all scores to the t snapshot). A block appends whole iterations
        only, so the count is per iteration."""
        gb = self._gbdt
        n_drop = (self._t_eff - (t + 1)) * gb.num_class
        if n_drop > 0:
            del gb.models[-n_drop:]
        dropped = self._t_eff - (t + 1)
        gb.iter -= dropped
        if gb.journal is not None and dropped > 0:
            gb.journal.event("truncate", iteration=int(gb.iter),
                             dropped_iters=int(dropped),
                             reason="early_stop_block")
        gb._journal_quality()  # snap the split ledger to the kept trees

    def set_scores_at(self, t, with_train=False):
        """Point every bound updater's score at the post-iteration-t
        state (t 0-based within the block). The train updater only
        moves when with_train (train-set metrics requested, or fixing
        state on an early-stop break) — its canonical final value comes
        from the scan itself."""
        for st in self._states[1:]:
            st["updater"].score = self._row_at(st, t)
        if with_train:
            st = self._states[0]
            st["updater"].score = self._row_at(st, t)

    def finalize(self):
        """After a COMPLETED walk (no early-stop break): restore the
        train score to the scan's final value, or — after a natural
        stop (an empty tree mid-block) — rebuild exact state for the
        kept trees (the walk's last snapshot is every valid set's)."""
        gb = self._gbdt
        if not self._natural_stop:
            gb.train_score_updater.score = self._scan_final_train
            return False
        Log.info("Stopped training because there are no more leafs "
                 "that meet the split requirements.")
        if gb._natural_stop_score_exact():
            gb.train_score_updater.score = self._scan_final_train
        else:
            gb._rebuild_train_score_from_models()
        return True


class GBDT:
    name = "gbdt"

    def __init__(self):
        self.models = _VersionedList()  # Tree list, class-major per iteration
        self.iter = 0
        self.num_init_iteration = 0
        self.num_iteration_for_pred = 0
        self.num_class = 1
        self.sigmoid = -1.0
        self.label_idx = 0
        self.max_feature_idx = 0
        self.feature_names = []
        self.train_data = None
        self.config = None
        self.objective = None
        self.tree_learner = None
        self.train_score_updater = None
        self.valid_score_updaters = []
        self.valid_metrics = []
        self.training_metrics = []
        self.early_stopping_round = 0
        self.shrinkage_rate = 0.1
        self.best_iter = []
        self.best_score = []
        self.best_msg = []
        self._bag_rows = None       # in-bag float mask or None
        self._bag_window = None     # it // bagging_freq of the cached bag
        self.last_compile_cache_hit = False  # persistent-cache hit on
        #                             the latest fused-program lowering
        # per-Booster telemetry (telemetry/): two Boosters in one
        # process keep their own span totals
        self.tracer = SpanTracer()
        self.metrics = MetricsRegistry()
        self.journal = None         # RunJournal when `telemetry` is on
        self._trainz_server = None
        # model-quality observability (telemetry/quality.py): the split
        # ledger tracker (`quality_telemetry` knob) and the training
        # dataset's baseline distribution (io/profile.py), persisted
        # next to every saved model file for the serving drift monitor
        self.quality = None
        self.dataset_profile = None
        self._last_metric_values = {}
        # collective latency/overlap attribution (`comm_telemetry`
        # knob; telemetry/comm_profile.py): fed by the heartbeat
        # timing sink, flushed into one `comm` journal record per
        # iteration/block
        self.comm_profile = None

    # ------------------------------------------------------------------ init
    def init(self, config, train_data, objective, training_metrics=()):
        """The objective is init'ed by the caller, before: its own
        set-up (`rank_layout`) is no part of the span `booster_init`
        on the process tracer."""
        self.iter = 0
        self.num_class = config.num_class
        self.config = None
        self.train_data = None
        with PROCESS_TRACER.span("booster_init", rows=train_data.num_data,
                                 features=train_data.num_features,
                                 classes=self.num_class):
            self.reset_training_data(config, train_data, objective,
                                     training_metrics)

    def reset_training_data(self, config, train_data, objective, training_metrics=()):
        """gbdt.cpp:42-115."""
        if self.train_data is not None and not self.train_data.check_align(train_data):
            Log.fatal("cannot reset training data, since new training data has "
                      "different bin mappers")
        self.early_stopping_round = config.early_stopping_round
        self.shrinkage_rate = config.learning_rate
        self.objective = objective
        self.apply_predict_config(config)
        self._bag_fn = None   # bakes in config/metadata; rebuild lazily
        self._bag_rows = None
        self._bag_window = None
        self.sigmoid = -1.0
        if objective is not None and objective.name == "binary":
            self.sigmoid = config.sigmoid

        # compiled fused programs bake in the old learner's bins and the
        # old objective's labels; never reuse them across a reset
        self._fused_cache = {}
        data_changed = train_data is not None and train_data is not self.train_data
        if data_changed:
            # process spans, children of `booster_init` under init():
            # row padding, word packing, bins to the device; the score
            with PROCESS_TRACER.span("learner"):
                if self.tree_learner is None:
                    self.tree_learner = create_tree_learner(
                        config.tree_learner, config)
                else:
                    self.tree_learner.config = config
                self.tree_learner.init(train_data)
            self.training_metrics = list(training_metrics)
            with PROCESS_TRACER.span("score"):
                self.train_score_updater = ScoreUpdater(train_data,
                                                        self.num_class)
                # replay THIS booster's trees onto the new data; merged
                # init trees are covered by the dataset's init score
                # (gbdt.cpp:77-79)
                for i in range(self.iter):
                    for k in range(self.num_class):
                        t = self.models[(i + self.num_init_iteration)
                                        * self.num_class + k]
                        self.train_score_updater.add_score_by_tree(t, k)
            self.num_data = train_data.num_data
            self.max_feature_idx = train_data.num_total_features - 1
            self.label_idx = train_data.label_idx
            self.feature_names = list(train_data.feature_names)
            # the dataset's training-time baseline distribution rides
            # with the booster so save_model_to_file can persist it
            # next to the model text (docs/Observability.md)
            self.dataset_profile = getattr(train_data, "profile", None)
        self.train_data = train_data
        self.config = config
        # data_changed already init'ed the learner with this config
        if self.tree_learner is not None and not data_changed:
            self.tree_learner.reset_config(config)
        if self.tree_learner is not None:
            # learners account host<->device transfer bytes into the
            # booster's registry (parallel/learners.py)
            self.tree_learner.metrics = self.metrics
        self._setup_telemetry(config)

    def add_valid_dataset(self, valid_data, valid_metrics):
        """gbdt.cpp:117-147."""
        if not self.train_data.check_align(valid_data):
            Log.fatal("cannot add validation data, since it has different bin "
                      "mappers with training data")
        updater = ScoreUpdater(valid_data, self.num_class)
        # only this booster's own trees: merged init trees are covered by
        # the valid set's init score (gbdt.cpp:125-129)
        for i in range(self.iter):
            for k in range(self.num_class):
                idx = (i + self.num_init_iteration) * self.num_class + k
                updater.add_score_by_tree(self.models[idx], k)
        self.valid_score_updaters.append(updater)
        self.valid_metrics.append(list(valid_metrics))
        if self.early_stopping_round > 0:
            self.best_iter.append([0] * len(valid_metrics))
            self.best_score.append([K_MIN_SCORE] * len(valid_metrics))
            self.best_msg.append([""] * len(valid_metrics))

    # ------------------------------------------------------------- telemetry
    def _setup_telemetry(self, config):
        """Wire the `telemetry_*` knobs (docs/Observability.md): the
        structured run journal (rank-suffixed JSONL in `telemetry_dir`),
        the collective sync-wait timing sink, and the opt-in /trainz
        endpoint. Idempotent per booster — a reset_parameter() config
        rebuild must not open a second journal."""
        # read again at close_telemetry; stored so a reset_parameter()
        # rebuild keeps the latest value
        self._telemetry_trace = bool(getattr(config, "telemetry_trace",
                                             False))
        # quality telemetry works with or without the journal: the
        # split-ledger tracker always feeds the registry gauges
        # (/trainz + Prometheus); `quality` journal records need
        # `telemetry` on too
        if (getattr(config, "quality_telemetry", False)
                and self.quality is None and self.train_data is not None):
            from ..telemetry.quality import QualityTracker
            self.quality = QualityTracker(self.max_feature_idx + 1,
                                          self.feature_names)
        if not getattr(config, "telemetry", False):
            return
        import weakref
        ref = weakref.ref(self)  # process-global sinks and the /trainz
        #                          thread must not pin a dropped booster
        if (self.comm_profile is None
                and getattr(config, "comm_telemetry", True)):
            from ..telemetry.comm_profile import CommProfiler
            self.comm_profile = CommProfiler(rank=faults.current_rank())

        def timing_sink(name, seconds):
            gbdt = ref()
            if gbdt is None:
                # the booster died without close_telemetry (Python-API
                # drop): self-unbind so guarded sections elsewhere in
                # the process go back to the zero-overhead path — if
                # this sink is still being called, it IS the bound one
                heartbeat.bind_timing_sink(None)
                return
            gbdt.metrics.observe("sync_wait_s", seconds)
            if gbdt.comm_profile is not None:
                gbdt.comm_profile.record(name, seconds)

        # collective sync-wait seconds land in the registry + the comm
        # profiler: binding the sink is what makes every guarded
        # section measure, armed watchdog or not
        # (parallel/heartbeat.py)
        heartbeat.bind_timing_sink(timing_sink)
        self._timing_sink_fn = timing_sink
        if self.comm_profile is not None:
            prof = self.comm_profile
            # publish this rank's cumulative collective wait in the
            # heartbeat beats so peers/aggregators compute straggler
            # deltas (comm_profile.straggler_deltas); holds the
            # profiler, not the booster — cleared by close_telemetry
            # and heartbeat.shutdown

            def beat_extra():
                return {"comm_wait_s": round(prof.cum_wait_s, 6)}

            heartbeat.bind_beat_extra(beat_extra)
            self._beat_extra_fn = beat_extra
        if self.journal is None:
            directory = (getattr(config, "telemetry_dir", "")
                         or getattr(config, "snapshot_dir", ""))
            if not directory:
                Log.warning("telemetry=true but neither telemetry_dir "
                            "nor snapshot_dir is set; run journal "
                            "disabled")
            else:
                rank = faults.current_rank()
                self.journal = run_journal.RunJournal(
                    directory, rank=rank,
                    meta={"num_ranks": int(getattr(config, "num_machines",
                                                   1) or 1)})
                run_journal.set_current(self.journal)
                self.tracer.rank = rank
                # distributed tracing (telemetry/disttrace.py): the
                # process-default recorder shares the run journal, so
                # traced canary retrains (LGBM_TPU_TRACE_CTX from a
                # /fleetz-driven comparison) land `trace` records in
                # the same timeline; SpanTracer mirrors its spans into
                # any active context via this recorder
                self._trace_recorder = disttrace.configure(
                    journal=self.journal, rank=rank, service="train",
                    sample_rate=float(getattr(config,
                                              "trace_sample_rate",
                                              0.01) or 0.0),
                    slow_ms=float(getattr(config, "slow_request_ms",
                                          0.0) or 0.0),
                    slow_only=bool(getattr(config, "trace_slow_only",
                                           False)))
                # crash flight recorder (`blackbox` knob): ring +
                # registry + journal tail dumped on watchdog abort
                # (exit 117/118, parallel/heartbeat.py), SIGQUIT, and
                # unhandled serving exceptions
                if getattr(config, "blackbox", True):
                    flight = disttrace.FLIGHT.configure(directory,
                                                        rank=rank)
                    self._flight_armed = flight.enabled
                    tracer, metrics = self.tracer, self.metrics
                    jpath = self.journal.path
                    flight.add_source("spans",
                                      lambda: tracer.recent(None))
                    flight.add_source("metrics", metrics.snapshot)
                    flight.add_source(
                        "journal_tail",
                        lambda: run_journal.tail(jpath, n=20))
                    flight.install_sigquit()
        port = int(getattr(config, "telemetry_port", 0) or 0)
        if port > 0 and self._trainz_server is None:
            from ..telemetry import trainz

            def iteration_fn():
                gbdt = ref()
                return gbdt.iter if gbdt is not None else -1

            def quality_fn():
                gbdt = ref()
                if gbdt is None or gbdt.quality is None:
                    return None
                return gbdt.quality.snapshot()

            def comm_fn():
                gbdt = ref()
                if gbdt is None or gbdt.comm_profile is None:
                    return None
                return gbdt.comm_profile.snapshot()

            self._trainz_server = trainz.start_trainz(
                trainz.build_sources(
                    iteration_fn=iteration_fn,
                    tracer=self.tracer,
                    registry=self.metrics,
                    journal=self.journal,
                    quality_fn=(quality_fn if self.quality is not None
                                else None),
                    comm_fn=(comm_fn if self.comm_profile is not None
                             else None)),
                port=port)

    def _journal_iteration(self, **fields):
        """One journal record per completed iteration (or fused block —
        `block` carries the iteration count it covers); phase seconds
        ride as deltas so summing records reconstructs the run totals
        (bench.py)."""
        if self.journal is None:
            return
        self.journal.iteration(self.iter,
                               phases=self.tracer.delta_snapshot(),
                               **fields)
        self._journal_comm()
        self._journal_introspection()

    def _journal_comm(self):
        """One `comm` record per iteration/block (`comm_telemetry`
        knob): per-collective host-visible waits since the last record,
        the derived comm_overlap_pct, and registry gauges so /trainz +
        Prometheus carry the live values (telemetry/comm_profile.py)."""
        if self.comm_profile is None:
            return
        rec = self.comm_profile.flush(self.iter)
        if rec is None:
            return
        self.metrics.set("comm_overlap_pct", rec["overlap_pct"])
        self.metrics.set("comm_wait_s", rec["wait_s"])
        if self.journal is not None:
            self.journal.event("comm", **rec)

    def _journal_introspection(self):
        """Memory watermarks + newly-recorded jit lowerings, appended at
        every iteration/block boundary (the cadence docs/Observability.md
        documents). The sample is one /proc read + allocator-stats call
        (~microseconds) and the ledger drain hands each compile to the
        journal exactly once, so the boundary cost stays inside the <1%
        telemetry overhead bar (bench telemetry_probe)."""
        from ..telemetry import ledger
        mem = ledger.sample_memory()
        if mem:
            self.journal.event("memory", iteration=int(self.iter), **mem)
            for key, val in mem.items():
                self.metrics.set(key, val)
        for entry in ledger.LEDGER.drain():
            self.journal.event("compile", label=entry["label"] or "jit",
                               seconds=round(entry["seconds"], 6),
                               cache_hit=bool(entry["cache_hit"]))

    def _journal_quality(self):
        """One `quality` record per completed iteration/block
        (`quality_telemetry` knob): the split ledger's deltas
        (splits/gain, top features by gain), the new trees' leaf-value
        distribution, the normalized-gain-importance L1 shift, and the
        latest eval metric values — the model-health timeline the
        serving drift monitor's data-health timeline pairs with.
        Registry gauges (quality_*) update even without a journal so
        /trainz + Prometheus always carry the totals."""
        if self.quality is None:
            return
        delta = self.quality.sync(self.models)
        ledger = self.quality.ledger
        self.metrics.set("quality_trees_total", int(ledger.n_trees))
        self.metrics.set("quality_splits_total", int(ledger.n_splits))
        self.metrics.set("quality_gain_total",
                         float(ledger.gain_sums.sum()))
        top = self.quality.snapshot()["top_features"]
        if top:
            self.metrics.set("quality_top_feature_gain",
                             float(top[0]["gain"]))
        if delta is not None and self.journal is not None:
            if self._last_metric_values:
                delta["values"] = dict(self._last_metric_values)
            self.journal.event("quality", iteration=int(self.iter),
                               **delta)

    @staticmethod
    def _rms(arr):
        a = np.asarray(arr, dtype=np.float64)
        return float(np.sqrt(np.mean(a * a))) if a.size else 0.0

    def finalize_introspection(self):
        """Final introspection drain: last memory/compile records, the
        `telemetry_trace` span-ring dump. The CLI
        calls it BEFORE writing `run_end` so that record stays the
        timeline's last event; close_telemetry runs it as a fallback
        for the Python-API path (engine/bench write no run_end).
        Once-only."""
        if self.journal is None or getattr(self, "_introspection_done",
                                           False):
            return
        self._introspection_done = True
        self._journal_introspection()
        if getattr(self, "_telemetry_trace", False):
            # the recent-span ring as ONE journal record: the trace
            # exporter (telemetry/export.py) renders it as
            # fine-grained per-thread slices next to the timeline
            self.journal.event("spans",
                               epoch_ts=self.tracer.epoch_wall,
                               spans=self.tracer.recent(n=None))

    def close_telemetry(self, merge=False):
        """End-of-run hook: drain the introspection layer (see
        finalize_introspection), close the journal (after an optional
        rank-0 merge) and stop the /trainz thread. Safe to call twice."""
        if self.journal is not None:
            self.finalize_introspection()
            # retire OUR trace recorder first: it shares the journal,
            # so its pending fragments must flush before close. A
            # newer booster's recorder stays installed
            rec = getattr(self, "_trace_recorder", None)
            if rec is not None:
                rec.flush_pending()
                if disttrace.get_recorder() is rec:
                    disttrace.set_recorder(None)
                self._trace_recorder = None
            if getattr(self, "_flight_armed", False):
                disttrace.FLIGHT.disarm()
                self._flight_armed = False
            if merge:
                run_journal.merge_journals(self.journal.directory)
            self.journal.close()
            if run_journal.current() is self.journal:
                run_journal.set_current(None)
            self.journal = None
        if self._trainz_server is not None:
            from ..telemetry import trainz
            trainz.stop_trainz(self._trainz_server)
            self._trainz_server = None
        # drop OUR process-global hooks (a newer booster's stay): an
        # unbound sink returns guarded sections to zero-overhead, and
        # beats must stop publishing a closed booster's frozen
        # comm_wait_s (wrong straggler attribution for peers)
        if (getattr(self, "_timing_sink_fn", None) is not None
                and heartbeat._TIMING_SINK is self._timing_sink_fn):
            heartbeat.bind_timing_sink(None)
        self._timing_sink_fn = None
        if (getattr(self, "_beat_extra_fn", None) is not None
                and heartbeat._BEAT_EXTRA is self._beat_extra_fn):
            heartbeat.bind_beat_extra(None)
        self._beat_extra_fn = None

    # --------------------------------------------------------------- bagging
    def _bagging_device_fn(self):
        """(iter, grad, hess) -> (M,) in-bag mask, fully in-graph —
        record- or query-unit bagging (gbdt.cpp:150-201) with an exact
        bag count via jax.random.permutation, keyed on
        (bagging_seed, iter // bagging_freq) so re-bagging happens at
        the reference's cadence and the fused scan and per-iteration
        loop draw identical bags. Returns None when bagging is off."""
        cfg = self.config
        if not (cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0):
            return None
        if getattr(self, "_bag_fn", None) is not None:
            return self._bag_fn
        n = self.num_data
        meta = self.train_data.metadata
        key = jax.random.PRNGKey(cfg.bagging_seed)
        freq = int(cfg.bagging_freq)
        qb = meta.query_boundaries
        if qb is None:
            bag_cnt = int(cfg.bagging_fraction * n)

            def fn(it, gradients=None, hessians=None):
                k = jax.random.fold_in(key, it // freq)
                mask = (jax.random.permutation(k, n) < bag_cnt)
                mask = mask.astype(jnp.float32)
                m = None if gradients is None else gradients.shape[-1]
                if m is not None and m > n:
                    mask = jnp.pad(mask, (0, m - n))
                return mask
        else:
            nq = len(qb) - 1
            bag_q = int(nq * cfg.bagging_fraction)
            row_q = np.searchsorted(np.asarray(qb), np.arange(n),
                                    side="right") - 1
            row_q_dev = jnp.asarray(row_q, jnp.int32)

            def fn(it, gradients=None, hessians=None):
                k = jax.random.fold_in(key, it // freq)
                qmask = (jax.random.permutation(k, nq) < bag_q)
                mask = jnp.take(qmask.astype(jnp.float32), row_q_dev)
                m = None if gradients is None else gradients.shape[-1]
                if m is not None and m > n:
                    mask = jnp.pad(mask, (0, m - n))
                return mask

        self._bag_fn = fn
        return fn

    def _bagging(self, it, gradients=None, hessians=None):
        """gbdt.cpp:150-201; returns in-bag float mask or None.
        gradients/hessians are provided for gradient-based sampling
        strategies (models/goss.py); plain bagging ignores them."""
        fn = self._bagging_device_fn()
        if fn is None:
            return None
        # cache keyed by the re-bag window (fused blocks and rollbacks
        # can move self.iter across windows between sequential calls)
        window = it // self.config.bagging_freq
        if window == self._bag_window and self._bag_rows is not None:
            return self._bag_rows
        mask = np.asarray(fn(jnp.int32(it)))[:self.num_data]
        Log.debug("Re-bagging, using %d data to train", int(mask.sum()))
        self._bag_rows = mask
        self._bag_window = window
        return mask

    # -------------------------------------------------------------- training
    def train_one_iter(self, gradients=None, hessians=None, is_eval=True):
        """gbdt.cpp:210-245. Returns True if training should stop."""
        faults.crash_if_reached(self.iter)
        faults.rank_crash_if_reached(self.iter)
        faults.rank_hang_if_reached(self.iter)
        heartbeat.WATCHDOG.set_iteration(self.iter)
        if gradients is None or hessians is None:
            if self.objective is None:
                Log.fatal("No object function provided")
            with self.tracer.phase("gradients"):
                gradients, hessians = self.objective.get_gradients(
                    self._score_for_boosting())
            self._note_rank_pairs(1)
        else:
            gradients = np.asarray(gradients, dtype=np.float32).reshape(
                self.num_class, self.num_data)
            hessians = np.asarray(hessians, dtype=np.float32).reshape(
                self.num_class, self.num_data)
        gradients, hessians = faults.poison_gradients_if_armed(
            self.iter, gradients, hessians)
        policy = getattr(self.config, "nonfinite_guard", "raise")
        if policy != "off":
            gradients, hessians, skip = guardrails.guard_gradients(
                gradients, hessians, self.iter, policy)
            if skip:
                # round skipped: no tree appended and self.iter does NOT
                # advance (the model list must stay iter*num_class long).
                # Callers loop over a bounded round count, so a
                # persistently-poisoned objective stalls progress but
                # cannot loop forever.
                return False
        with self.tracer.phase("bagging"):
            inbag = self._bagging(self.iter, gradients, hessians)
        n = self.num_data
        multi_host = getattr(self.tree_learner, "n_proc", 1) > 1
        linear = bool(getattr(self.config, "linear_tree", False))
        new_leaves = 0
        self._note_builder_kernels()
        for k in range(self.num_class):
            with self.tracer.phase("build"):
                out = self.tree_learner.train_device(
                    gradients[k], hessians[k], inbag)
            self._count_trees(1)
            if linear:
                # the split search fixed the STRUCTURE; now refit every
                # eligible leaf as a ridge model over its path features
                # (models/linear_leaves.py). This path is host-synced by
                # construction — the fit needs the partition and the
                # gradients on host — so laziness buys nothing here.
                with self.tracer.phase("host_sync"), \
                        heartbeat.collective_guard("leaf_count_sync"):
                    tree, lin_values = self._fit_linear_tree(
                        out, gradients[k], hessians[k], inbag)
                with self.tracer.phase("score_upd"):
                    self.train_score_updater.add_score_by_values(
                        lin_values * self.shrinkage_rate, k)
                    for updater in self.valid_score_updaters:
                        updater.add_score_by_tree(tree, k)
                stopped = tree.num_leaves <= 1
            else:
                # enqueue ALL device work for this class before the scalar
                # stop check: train scores via partition gather (covers
                # in-bag AND out-of-bag rows: the partition is computed
                # over all rows, the bag mask only gates the histogram
                # statistics), then valid scores via device bin-space
                # traversal. A 0-split tree makes every update a no-op
                # (leaf values are all zero), so checking afterwards is
                # safe.
                tree = LazyTree(out, self.tree_learner,
                                shrink=self.shrinkage_rate)
                with self.tracer.phase("score_upd"):
                    self.train_score_updater.add_score_by_partition(
                        self.tree_learner.local_leaf_values(out)
                        * self.shrinkage_rate,
                        self.tree_learner.local_row_leaf(out, n), k)
                    for updater in self.valid_score_updaters:
                        if multi_host:
                            # device-tree traversal would mix global and
                            # local arrays; materialize once and score on
                            # host
                            updater.add_score_by_tree(tree, k)
                        else:
                            updater.add_score_by_device_tree(
                                out, self.shrinkage_rate, k)
                with self.tracer.phase("host_sync"), \
                        heartbeat.collective_guard("leaf_count_sync"):
                    stopped = tree.num_leaves <= 1  # scalar sync: only wait
            # collective-byte ledger: the meshed learners' wire plan is
            # root + per-split x n_splits (parallel/mesh.py CommPlan);
            # n_splits is on host from the sync above, so the counters
            # advance exactly once per tree — including 0-split trees,
            # whose root exchange still moved bytes
            account = getattr(self.tree_learner,
                              "account_tree_collectives", None)
            if account is not None:
                account(tree.num_leaves - 1)
            if stopped:
                Log.info("Stopped training because there are no more leafs "
                         "that meet the split requirements.")
                # an iteration is K trees or none: the model list stays
                # iter * num_class long, class-major (callers may go on
                # calling after a stop, and every reader indexes it by
                # class), so the earlier classes' trees are taken back
                self._take_back_trees(k)
                return True
            new_leaves += tree.num_leaves
            self.models.append(tree)
        self.iter += 1
        self.metrics.inc("leaves_total", new_leaves)
        self.metrics.set("iteration", self.iter)
        if self.journal is not None:
            # norms are the per-iteration training-health proxy (a NaN
            # storm or divergence is visible before the guardrails
            # fire); np transfer is (K, N) f32, telemetry-gated.
            # Learners with per-iteration IO telemetry (the out-of-core
            # streaming learner's prefetch deltas) ride along through
            # the journal_fields hook.
            fields_fn = getattr(self.tree_learner, "journal_fields", None)
            extra = fields_fn() if callable(fields_fn) else {}
            self._journal_iteration(grad_norm=self._rms(gradients),
                                    hess_norm=self._rms(hessians),
                                    leaf_count=int(new_leaves),
                                    **(extra or {}))
        self._journal_quality()
        if is_eval:
            with self.tracer.phase("eval"):
                return self.eval_and_check_early_stopping()
        return False

    def _score_for_boosting(self):
        """Hook for DART's tree-dropping (dart.hpp GetTrainingScore)."""
        return self.train_score_updater.score

    def _fit_linear_tree(self, out, grad, hess, inbag):
        """Materialize the builder's tree and refit its leaves as ridge
        models (models/linear_leaves.py, docs/Linear-Trees.md).

        Returns (tree, values): the SHRUNK materialized tree and the
        UNSHRUNK per-row (N,) f64 outputs (the caller applies the
        learning rate to the score delta, mirroring the constant path's
        `leaf_values * shrinkage_rate`). The fit runs in unshrunk value
        space and the whole model block scales multiplicatively, so
        shrinkage/DART semantics match constant leaves exactly."""
        from .linear_leaves import fit_linear_leaves, leaf_path_features
        learner = self.tree_learner
        n = self.num_data
        tree = learner._to_host_tree(out, shrink=1.0)
        if tree.num_leaves <= 1:
            tree.shrinkage(self.shrinkage_rate)
            return tree, np.zeros(n, np.float64)
        row_leaf = np.asarray(learner.local_row_leaf(out, n))
        feats = leaf_path_features(
            tree.split_feature, tree.left_child, tree.right_child,
            tree.leaf_parent, tree.num_leaves,
            self.config.linear_max_features)
        chunks, bin_values, fit_chunk = learner.linear_fit_context()
        const, coeffs, is_lin, values = fit_linear_leaves(
            feats, tree.leaf_value, tree.leaf_count, bin_values,
            row_leaf, np.asarray(grad)[:n], np.asarray(hess)[:n],
            None if inbag is None else np.asarray(inbag)[:n],
            chunks, fit_chunk, self.config.linear_lambda)
        if is_lin.any():
            tree.set_linear(const, coeffs, is_lin, feats,
                            learner.train_set.real_feature_idx)
        tree.shrinkage(self.shrinkage_rate)
        return tree, values

    # ------------------------------------------------- fused multi-iteration
    # TPU-first: when nothing in an iteration needs the host (no bagging,
    # no per-iteration metric output, binary/regression with a jitted
    # gradient), the ENTIRE boosting block — gradients, tree build, score
    # update — runs as ONE XLA program: a lax.scan over iterations. The
    # host's only job is to feed the per-iteration feature-fraction masks
    # (same RNG stream as the sequential path) and pull the stacked tree
    # arrays once at the end. The reference's C++ hot loop
    # (gbdt.cpp:210-245) keeps everything in-process; this keeps
    # everything in-graph.

    def _fused_boosting_ok(self):
        """Whether this boosting type's per-iteration logic is pure
        in-graph work. DART's tree dropping mutates the model list on
        host; GOSS overrides this (its sampling runs in-graph via
        _fused_inbag_fn)."""
        return type(self).__name__ == "GBDT"

    def _fused_inbag_fn(self):
        """Optional (iter, grad, hess) -> (N_pad,) in-bag weights hook
        for the fused scan (grad/hess are (K, N_pad) padded); None =
        constant all-ones. The caller masks padding rows afterwards.
        Plain bagging fuses via its in-graph mask; GOSS overrides."""
        return self._bagging_device_fn()

    def _fused_eligible(self, ignore_train_metrics=False):
        """ignore_train_metrics=True answers "could this train fused in
        metric_freq-sized blocks, with metric output (and valid-set
        score catch-up from the block's materialized trees) between
        blocks?" (the CLI uses it, application.py train)."""
        cfg = self.config
        if cfg is None or self.objective is None:
            return False
        return (self._fused_boosting_ok()
                and (not self.valid_score_updaters or ignore_train_metrics)
                and (cfg.metric_freq <= 0 or not self.training_metrics
                     or ignore_train_metrics)
                and self.early_stopping_round <= 0
                and getattr(self.objective, "_grad_pure", None) is not None
                # linear leaves refit on host AFTER each structure, and
                # the refit changes the residuals the next iteration
                # sees — the scan cannot bake that in. train_many falls
                # back to the per-iteration loop transparently.
                and not bool(getattr(cfg, "linear_tree", False))
                and self._fused_learner_ok())

    def _fused_learner_ok(self):
        """Whether the learner has a score layout for the scan
        (learner.score_layout): the serial learner's (K, N) score as it
        is, or the data-parallel one's padded and row-sharded as the
        bins are, so each shard's rows stay on their chip for the whole
        block. A sharded layout also pads the gradients' operands, so
        an objective whose operands are not per-row arrays (a ranking
        layout) keeps the per-iteration loop there."""
        layout = self.tree_learner.score_layout()
        if layout is None:
            return False
        if layout[1] is None:
            return True
        n = self.num_data
        return all(np.ndim(a) >= 1 and np.shape(a)[-1] == n for a in
                   jax.tree_util.tree_leaves(self.objective._grad_ops))

    def _note_rank_pairs(self, iterations):
        """A ranking objective's pairwise work, `iterations` times: the
        registry counters `rank_pairs` (ordered pairs of two documents
        of one query) and `rank_pair_slots` (slots of the pair tensors
        that evaluate them, objectives/rank_device.py), and their ratio
        as the gauge `rank_pair_fill` in /trainz."""
        layout = getattr(self.objective, "layout", None)
        if layout is not None and iterations > 0:
            self.metrics.inc("rank_pairs", layout.pairs * iterations)
            self.metrics.inc("rank_pair_slots",
                             layout.pair_slots * iterations)
            self.metrics.set("rank_pair_fill",
                             layout.pairs / max(layout.pair_slots, 1))

    def _class_axis_form(self):
        """How the fused step runs the K trees of an iteration: `single`
        (K = 1), `scan` (the leaf-contiguous and the gather-compacted
        builders dispatch histogram work through a bucketed lax.switch:
        vmapped over the class axis it would execute EVERY bucket branch
        per split, so those scan the classes), `vmap` (the masked
        builder)."""
        if self.num_class == 1:
            return "single"
        learner = self.tree_learner
        return ("scan" if getattr(learner, "_use_partitioned", False)
                or getattr(learner, "_use_compact", False) else "vmap")

    def _count_trees(self, grown):
        """Trees handed to the builder: `tree_build_dispatches`, and of
        them `class_trees`, those of an iteration that grows one a
        class."""
        self.metrics.inc("tree_build_dispatches", grown)
        if self.num_class > 1:
            self.metrics.inc("class_trees", grown)

    def _hist_min_rows(self):
        """Rows of the lowest rung of the partitioned builder's
        histogram ladder (ops/ordered_hist.py min_rows), from the
        learner's packed word rows and bins."""
        from ..ops.ordered_hist import min_rows
        learner = self.tree_learner
        return min_rows(4 * int(learner._bins.shape[0]), learner.max_bin)

    def _count_hist_calls(self, trees):
        """The histogram ladder's hit share, from trees already on the
        host (each a dict of one tree's arrays): `seg_hist_calls`, one a
        split (its smaller child's histogram), and of them
        `seg_hist_subchunk_calls`, those whose smaller child has fewer
        rows than HIST_CHUNK, which a rung under a chunk can take (0
        where the ladder has none)."""
        from ..ops.pallas_hist import HIST_CHUNK
        sub = self._hist_min_rows() < HIST_CHUNK
        calls = small = 0
        for tree in trees:
            k = int(tree["n_splits"])
            counts = [np.where(child >= 0,
                               tree["internal_count"][np.maximum(child, 0)],
                               tree["leaf_count"][np.maximum(~child, 0)])
                      for child in (np.asarray(tree["left_child"])[:k],
                                    np.asarray(tree["right_child"])[:k])]
            calls += k
            if sub:
                small += int(np.sum(np.minimum(*counts) < HIST_CHUNK))
        self.metrics.inc("seg_hist_calls", calls)
        self.metrics.inc("seg_hist_subchunk_calls", small)

    def _note_builder_kernels(self, fused=False):
        """The class axis, as registry gauges in /trainz:
        `trees_per_iteration` (K) and `class_axis_form` (`single`; in the
        fused step `scan` or `vmap`, _class_axis_form; `loop`, a host
        loop, in the per-iteration path). And what the partitioned
        builder's kernels compile to, beside `tree_build_dispatches`:
        `partition_engine` (ops/partition.py: `pallas` on a TPU, `xla`
        off it), and the histogram kernel's streamed operand
        (ops/ordered_hist.py onehot_extent): `seg_hist_onehot_rows` a
        feature and `seg_hist_features_per_dot`, and `seg_hist_low_bins`
        (low_bins: L of the split-bin form, 0 where the one-hot form
        runs); the grid's feature axis
        (feature_blocks): `seg_hist_feature_blocks` a call (1: no such
        axis) and `seg_hist_block_features`; the histogram's ladder
        (min_rows, hist_rungs): `seg_hist_min_rows`, the rows of its
        lowest rung, and `seg_hist_rungs`, the branches a call site
        compiles; what the partition kernel
        was sized to (pack_rows, chunk_lanes): `partition_rows_words` a
        row and `partition_rows_chunk_lanes` a DMA, and how it moves a
        tile's rows, `partition_rows_tile_form` (TILE_FORM); how the
        position -> leaf map is made, `pos_leaf_form` (POS_LEAF_FORM:
        `once_a_tree`, from the segment bounds after the split loop); and
        `score_update_form`, the end-of-tree un-permute and leaf-value
        lookup that were traced (ops/partition.py unpermute, one
        key-value sort, + score_updater.py lookup_form:
        `sort_kv+split64` at 255 leaves)."""
        self.metrics.set("trees_per_iteration", int(self.num_class))
        self.metrics.set("mesh_devices",
                         int(getattr(self.tree_learner, "n_shards", 1)))
        self.metrics.set(
            "class_axis_form", self._class_axis_form()
            if fused or self.num_class == 1 else "loop")
        if getattr(self.tree_learner, "_use_partitioned", False):
            from ..ops.ordered_hist import (feature_blocks, hist_rungs,
                                            low_bins, onehot_extent)
            from ..ops.pallas_hist import HIST_CHUNK
            from ..ops.partition import (POS_LEAF_FORM, TILE_FORM,
                                         chunk_lanes, packed_word_rows,
                                         partition_engine)
            self.metrics.set("partition_engine", partition_engine())
            self.metrics.set(
                "score_update_form", "sort_kv+"
                + lookup_form(int(self.tree_learner.config.num_leaves)))
            rows, features = onehot_extent(self.tree_learner.max_bin)
            self.metrics.set("seg_hist_onehot_rows", rows)
            self.metrics.set("seg_hist_features_per_dot", features)
            self.metrics.set("seg_hist_low_bins",
                             low_bins(self.tree_learner.max_bin))
            words = int(self.tree_learner._bins.shape[0])
            blocks, block_features = feature_blocks(
                4 * words, self.tree_learner.max_bin)
            self.metrics.set("seg_hist_feature_blocks", blocks)
            self.metrics.set("seg_hist_block_features", block_features)
            r = self._hist_min_rows()
            self.metrics.set("seg_hist_min_rows", r)
            rows = int(self.tree_learner._bins.shape[1])  # of one shard
            if getattr(self.tree_learner, "shard_rows", False):
                rows //= self.tree_learner.n_shards
            self.metrics.set("seg_hist_rungs",
                             len(hist_rungs(rows // HIST_CHUNK, r)))
            wp = packed_word_rows(words)
            self.metrics.set("partition_rows_words", wp)
            self.metrics.set("partition_rows_chunk_lanes", chunk_lanes(wp))
            self.metrics.set("partition_rows_tile_form", TILE_FORM)
            self.metrics.set("pos_leaf_form", POS_LEAF_FORM)

    def _get_fused_fn(self, num_iters):
        if not hasattr(self, "_fused_cache"):
            self._fused_cache = {}
        learner_shapes = (self.tree_learner.num_data, self.tree_learner.n_pad,
                          self.tree_learner.f_pad)
        key = (num_iters, float(self.shrinkage_rate), id(self.tree_learner),
               learner_shapes, id(self.objective))
        if key in self._fused_cache:
            return self._fused_cache[key]
        learner = self.tree_learner
        n, n_pad = learner.num_data, learner.n_pad
        pad = n_pad - n
        core = learner._build_core
        shrink = jnp.float32(self.shrinkage_rate)
        # every data-dependent array rides as a runtime ARGUMENT of the
        # compiled program, not a closure: closed-over arrays embed
        # their VALUES in the lowered HLO, so two runs with (say)
        # different labels would hash to different persistent-cache
        # entries and recompile. With the operands as arguments the
        # program bytes depend only on shapes/dtypes — one lowered
        # executable per (shape bucket, config) per machine.
        # (every fused-eligible objective has the pure form,
        # _fused_eligible)
        grad_pure = self.objective._grad_pure
        # a row-sharded learner (learner.score_layout): the score, the
        # gradients' per-row operands and the in-bag weights padded to
        # N_pad and placed as the bins are, so the block's row work
        # stays on each row's chip; `rows` is what a row -> leaf map is
        # cut to for the score update
        rows, sharding = learner.score_layout()
        if sharding is None:
            inbag = jnp.concatenate([jnp.ones(n, jnp.float32),
                                     jnp.zeros(pad, jnp.float32)])
            gops = self.objective._grad_ops
        else:
            self.train_score_updater.set_row_layout(rows, sharding)
            inbag = learner.pad_rows_sharded(jnp.ones(n, jnp.float32))
            gops = jax.tree_util.tree_map(learner.pad_rows_sharded,
                                          self.objective._grad_ops)
        data = {
            "bins": learner._bins,
            "nbpf": learner._num_bin_pf,
            "iscat": learner._is_cat,
            "inbag": inbag,
            "gops": gops,
        }

        self._note_builder_kernels(fused=True)
        class_axis = self._class_axis_form()
        inbag_fn = self._fused_inbag_fn()

        def fused(score, fmasks, iters, d):
            bins, nbpf, iscat, inbag = (d["bins"], d["nbpf"], d["iscat"],
                                        d["inbag"])

            def step(score, xs):
                fmask, it = xs  # fmask: (K, F) — one mask PER CLASS
                # TREE, matching the sequential path's per-tree feature
                # sampling (serial_tree_learner.cpp:160-165)
                # device scopes (telemetry/trace.py DEVICE_SCOPES): the
                # builder writes its own between these two
                with scope("gradients"):
                    g, h = grad_pure(d["gops"], score)
                    gp = jnp.pad(g, ((0, 0), (0, n_pad - g.shape[1])))
                    hp = jnp.pad(h, ((0, 0), (0, n_pad - h.shape[1])))
                    # per-iteration in-bag weights (GOSS); pad rows stay
                    # zero
                    ib = (inbag if inbag_fn is None
                          else inbag_fn(it, gp, hp) * inbag)
                if class_axis == "single":
                    out = core(bins, gp[0], hp[0], ib, fmask[0], nbpf,
                               iscat)
                    with scope("score_update"):
                        upd = leaf_lookup(out["leaf_value"] * shrink,
                                          out["row_leaf"][:rows])[None, :]
                elif class_axis == "vmap":
                    # one device program for ALL classes: vmap the
                    # whole-tree builder over the class axis (SURVEY M2;
                    # the reference loops classes serially,
                    # gbdt.cpp:210-245)
                    out = jax.vmap(
                        lambda gg, hh, fm: core(bins, gg, hh, ib, fm,
                                                nbpf, iscat))(gp, hp, fmask)
                    with scope("score_update"):
                        # a class at a time: batched over K the lookup
                        # compiles to the generic gather again
                        upd = jax.lax.map(
                            lambda o: leaf_lookup(o[0] * shrink, o[1][:rows]),
                            (out["leaf_value"], out["row_leaf"]))
                else:
                    # scan the class axis (_class_axis_form has why):
                    # one compiled program, the reference's sequential
                    # class loop. Every class's tree grows from the
                    # iteration's one gradient pass above.
                    def class_step(_, gh):
                        gg, hh, fm = gh
                        o = core(bins, gg, hh, ib, fm, nbpf, iscat)
                        # the step's own update is all that reads the
                        # row -> leaf map: it never joins the stacked ys
                        row_leaf = o.pop("row_leaf")
                        with scope("score_update"):
                            u = leaf_lookup(o["leaf_value"] * shrink,
                                            row_leaf[:rows])
                        return None, (o, u)

                    # a path component, no scope of its own
                    # (telemetry/trace.py DEVICE_PATH_WORDS)
                    with scope("class_scan"):
                        _, (out, upd) = jax.lax.scan(class_step, None,
                                                     (gp, hp, fmask))
                with scope("score_update"):
                    score = score + upd
                out.pop("row_leaf", None)  # keep the ys O(iter * num_leaves)
                return score, out

            return jax.lax.scan(step, score, (fmasks, iters))

        score = self.train_score_updater.carried()
        fmasks = jnp.ones((num_iters, self.num_class, learner.f_pad),
                          dtype=bool)
        iters = jnp.arange(num_iters, dtype=jnp.int32)
        from ..config import compile_cache_hits
        from ..telemetry.ledger import LEDGER
        hits_before = compile_cache_hits()
        # the compile ledger attributes this lowering to its shape
        # bucket — the fused scan length is what keys recompiles — in
        # two labels whose wall seconds it keeps: `:lower` (trace +
        # lower, which no cache serves) and `:compile` (backend compile,
        # or the load from the persistent cache).
        bucket = f"fused_scan_{num_iters}it"
        with LEDGER.label(bucket + ":lower"):
            lowered = jax.jit(fused).lower(score, fmasks, iters, data)
        with LEDGER.label(bucket + ":compile"):
            compiled = lowered.compile()
        # whether the persistent compile cache served this lowering —
        # surfaced by bench.py as phases.compile_cache_hit
        self.last_compile_cache_hit = compile_cache_hits() > hits_before
        if self.last_compile_cache_hit:
            # counted HERE, once per actual lowering — blocks reusing
            # the in-process runner never touch the persistent cache
            self.metrics.inc("compile_cache_hits")

        def runner(score, fmasks, iters):
            return compiled(score, fmasks, iters, data)

        self._fused_cache[key] = runner
        return runner

    def warm_up_fused(self, num_iters):
        """Pre-compile the fused trainer (compile time is not training
        time, same as the reference's ahead-of-time C++ build)."""
        if self._fused_eligible():
            self._get_fused_fn(num_iters)
            return True
        return False

    def _run_fused_block(self, num_iters):
        """Run ONE fused scan of `num_iters` iterations and append the
        materialized trees. Returns (stacked_device, t_eff, n_before):
        the block's stacked tree arrays still on device (for snapshot
        traversal), the number of full iterations kept, and the
        model-list length before the block. An iteration in which a
        class grew no tree ends the block and is dropped whole, its
        earlier classes' trees with it (train_one_iter does the same):
        the model list stays iter * num_class long. The train score is
        set to the scan's final score (which, at a natural stop, still
        includes discarded trees — callers fix that up)."""
        # a fused block is ONE device program: a preemption anywhere
        # inside it loses the whole block, which is exactly what
        # crashing at its launch models (utils/faults.py)
        faults.crash_if_reached(self.iter, num_iters)
        faults.rank_crash_if_reached(self.iter, num_iters)
        faults.rank_hang_if_reached(self.iter, num_iters)
        heartbeat.WATCHDOG.set_iteration(self.iter)
        fn = self._get_fused_fn(num_iters)
        learner = self.tree_learner
        tags = {"iterations": num_iters, "classes": self.num_class,
                "first_iter": self.iter}
        span = self.tracer.span
        # the whole block is one device program; its host-side waits
        # (score pull, stacked-tree transfer) are THE block-boundary
        # sync points the collective watchdog brackets. The child spans
        # name what the host does around that program (docs/
        # Observability.md): everything but `wait` is time the device
        # may sit idle for
        with span("fused_block", **tags):
            with span("masks", **tags):
                # same RNG stream and consumption order as the
                # sequential path: one mask per (iteration, class) tree
                fmasks = jnp.asarray(np.stack(
                    [[learner._sample_features()
                      for _ in range(self.num_class)]
                     for _ in range(num_iters)]))
                iters = jnp.arange(self.iter, self.iter + num_iters,
                                   dtype=jnp.int32)
            with heartbeat.collective_guard("fused_block"):
                with span("launch", **tags):
                    final_score, stacked = fn(
                        self.train_score_updater.carried(), fmasks, iters)
                    self.train_score_updater.set_carried(final_score)
                with span("wait", **tags):
                    # the guard's host read below would block here anyway
                    jax.block_until_ready(final_score)
                policy = getattr(self.config, "nonfinite_guard", "raise")
                if policy != "off":
                    # in-graph iterations cannot be guarded individually;
                    # the block boundary is where divergence becomes
                    # detectable
                    with span("guard", bytes=int(final_score.nbytes),
                              **tags):
                        # a row-sharded score lies on every chip: they
                        # check their own rows, and the host reads the
                        # score only to name a value that is not finite
                        if (learner.score_layout()[1] is None or not bool(
                                jnp.isfinite(final_score).all())):
                            guardrails.guard_scores(
                                np.asarray(final_score),
                                self.iter + num_iters, policy)
                with span("tree_fetch", **tags):
                    # ONE transfer for the block
                    host = jax.device_get(stacked)
            nsp = np.asarray(host["n_splits"]).reshape(num_iters, -1)
            empty = (nsp == 0).any(axis=1)       # nsp: (T, K)
            t_eff = (int(np.argmax(empty)) if bool(empty.any())
                     else num_iters)

            def slice_at(t, k):
                if self.num_class == 1:
                    return {key: v[t] for key, v in host.items()}
                return {key: v[t, k] for key, v in host.items()}

            n_before = len(self.models)
            with span("materialize", **tags):
                grown = [slice_at(t, k) for t in range(t_eff)
                         for k in range(self.num_class)]
                self.models.extend(
                    learner.host_out_to_tree(tree, shrink=self.shrinkage_rate)
                    for tree in grown)
        self.iter += t_eff
        self._note_rank_pairs(t_eff)
        self.metrics.inc("fused_blocks")
        self._count_trees(len(self.models) - n_before)
        if getattr(learner, "_use_partitioned", False):
            self._count_hist_calls(grown)
        account = getattr(learner, "account_tree_collectives", None)
        if account is not None:
            # the block's collectives, from its trees' split counts
            # (the learner's CommPlan: root + per split)
            for tree in grown:
                account(int(tree["n_splits"]))
        self.metrics.inc("transfer_bytes",
                         sum(np.asarray(v).nbytes for v in host.values()))
        self.metrics.set("iteration", self.iter)
        if self.journal is not None and t_eff > 0:
            # per-iteration host phases do not exist inside one XLA
            # program: the block record covers its t_eff iterations
            self._journal_iteration(
                block=int(t_eff), fused=True,
                compile_cache_hit=bool(self.last_compile_cache_hit))
        self._journal_quality()
        return stacked, t_eff, n_before

    def _natural_stop_score_exact(self):
        """At a natural stop (an empty tree mid-block), whether the
        scan's final score is already exact: constant in-bag weights and
        feature masks keep gradients unchanged, so every discarded tree
        was empty and added zero score."""
        return (self.num_class == 1 and self._fused_inbag_fn() is None
                and self.config.feature_fraction >= 1.0)

    def _rebuild_train_score_from_models(self):
        """Recompute the train score from the kept model list (used when
        a natural stop discards scan iterations whose score
        contributions were not zero)."""
        self.train_score_updater = ScoreUpdater(self.train_data,
                                                self.num_class)
        # skip merged/loaded init trees: the fresh updater's init
        # score already covers them (reset_training_data replays the
        # same range)
        first = self.num_init_iteration * self.num_class
        for idx in range(first, len(self.models)):
            self.train_score_updater.add_score_by_tree(
                self.models[idx], idx % self.num_class)

    def train_many(self, num_iters, ignore_train_metrics=False):
        """Train `num_iters` boosting iterations; uses the fused in-graph
        scan when eligible, else the per-iteration loop. Returns True if
        training stopped early. ignore_train_metrics runs the scan even
        with training metrics attached (the caller prints between
        blocks; application.py train)."""
        if num_iters <= 0:
            return False
        if not self._fused_eligible(ignore_train_metrics):
            for _ in range(num_iters):
                if self.train_one_iter():
                    return True
            return False
        _, t_eff, n_before = self._run_fused_block(num_iters)
        # valid scores stay in sync with the model list no matter who
        # called (the scan only carries TRAIN scores): one batched
        # update per valid set for the whole block
        if self.valid_score_updaters and len(self.models) > n_before:
            # whole iterations only: the slice is class-major
            new_trees = self.models[n_before:]
            with self.tracer.span("valid_update", iterations=t_eff,
                                  first_iter=self.iter - t_eff):
                for updater in self.valid_score_updaters:
                    updater.add_score_by_trees(new_trees, self.num_class)
        if t_eff < num_iters:
            Log.info("Stopped training because there are no more leafs "
                     "that meet the split requirements.")
            if self._natural_stop_score_exact():
                return True
            # multiclass (the stop iteration's other classes and the
            # scan's later iterations kept learning) or
            # per-iteration bag/feature sampling (a later sample can
            # split again): the scan's score includes discarded trees —
            # rebuild from the kept trees so booster state matches the
            # model list
            self._rebuild_train_score_from_models()
            return True
        return False

    def train_many_eval(self, num_iters):
        """Fused block + per-iteration score snapshots for metric replay
        (the engine's valid+early-stopping fast path: gbdt.cpp:210-349
        interleaves build and eval per iteration; here the whole block
        builds in ONE device program and the per-iteration valid/train
        scores are reconstructed afterwards from the block's stacked
        tree arrays by one vmapped device traversal per dataset chunk).

        Returns (t_eff, snapshots). Caller contract (engine.train):
        - walk t = 0..t_eff-1 forward, calling
          snapshots.set_scores_at(t) before evaluating metrics;
        - on an early-stop break at t: snapshots.set_scores_at(t,
          with_train=True) then snapshots.drop_tail_to(t);
        - on a completed walk: snapshots.finalize() — returns True at
          a natural stop (an empty tree ended the block early).
        Requires _fused_eligible(ignore_train_metrics=True)."""
        base_train = self.train_score_updater.score
        base_valids = [u.score for u in self.valid_score_updaters]
        stacked, t_eff, _ = self._run_fused_block(num_iters)
        snap = _BlockSnapshots(self, stacked, base_train, base_valids,
                               t_eff, natural_stop=t_eff < num_iters)
        return t_eff, snap


    def _take_back_trees(self, count):
        """Drop the last `count` trees, those of classes 0..count-1 of
        one iteration, and subtract what each added to its class's
        train and valid scores."""
        if count <= 0:
            return
        for k, tree in enumerate(self.models[len(self.models) - count:]):
            tree.shrinkage(-1.0)
            self.train_score_updater.add_score_by_tree(tree, k)
            for updater in self.valid_score_updaters:
                updater.add_score_by_tree(tree, k)
        del self.models[len(self.models) - count:]

    def rollback_one_iter(self):
        """gbdt.cpp:247-264. Indexes from the end of the model list so it
        stays valid after early-stopping truncation."""
        if self.iter == 0 or len(self.models) < self.num_class:
            return
        self._take_back_trees(self.num_class)
        self.iter -= 1
        if self.quality is not None:
            # snap the split ledger to the surviving trees NOW: a
            # retrained iteration restores the old list LENGTH, which
            # a later length-only sync could not tell from no change
            self.quality.sync(self.models)

    # ------------------------------------------------------------ evaluation
    def eval_and_check_early_stopping(self):
        """gbdt.cpp:266-281. Unlike the reference (which only pops the model
        list), the dropped trees' score contributions are also subtracted so
        the booster state stays consistent for rollback / continued use."""
        best_msg = self.output_metric(self.iter)
        if best_msg:
            Log.info("Early stopping at iteration %d, the best iteration round is %d",
                     self.iter, self.iter - self.early_stopping_round)
            Log.info("Output of best iteration round:\n%s", best_msg)
            self._truncate_iters(self.early_stopping_round)
            return True
        return False

    def _truncate_iters(self, k):
        """Drop the last k iterations, subtracting their score contributions
        in one batched pass per dataset (the reference only pops the model
        list, gbdt.cpp:271-279, leaving scores stale)."""
        k = min(k, self.iter)
        if k <= 0:
            return
        dropped = self.models[-k * self.num_class:]
        del self.models[-k * self.num_class:]
        self.iter -= k
        for updater in [self.train_score_updater] + self.valid_score_updaters:
            updater.sub_score_by_trees(dropped, self.num_class)
        if self.journal is not None:
            self.journal.event("truncate", iteration=int(self.iter),
                               dropped_iters=int(k), reason="early_stop")
        self._journal_quality()  # snap the split ledger to the kept trees

    def output_metric(self, it):
        """gbdt.cpp:292-349: print metrics, track early stopping."""
        need_output = self.config is not None and self.config.metric_freq > 0 \
            and (it % self.config.metric_freq) == 0
        ret = ""
        msg_lines = []
        met_pairs = []
        met_values = {}
        if need_output:
            for metric in self.training_metrics:
                scores = metric.eval(self.train_score_updater.host_score())
                for name, sc in zip(metric.names, scores):
                    line = f"Iteration:{it}, training {name} : {sc:g}"
                    Log.info("%s", line)
                    met_values[f"training {name}"] = float(sc)
                    if self.early_stopping_round > 0:
                        msg_lines.append(line)
        if need_output or self.early_stopping_round > 0:
            for i, metrics in enumerate(self.valid_metrics):
                for j, metric in enumerate(metrics):
                    scores = metric.eval(self.valid_score_updaters[i].host_score())
                    for name, sc in zip(metric.names, scores):
                        line = f"Iteration:{it}, valid_{i + 1} {name} : {sc:g}"
                        met_values[f"valid_{i + 1} {name}"] = float(sc)
                        if need_output:
                            Log.info("%s", line)
                        if self.early_stopping_round > 0:
                            msg_lines.append(line)
                    if not ret and self.early_stopping_round > 0:
                        cur = metric.factor_to_bigger_better * scores[-1]
                        if cur > self.best_score[i][j]:
                            self.best_score[i][j] = cur
                            self.best_iter[i][j] = it
                            met_pairs.append((i, j))
                        elif it - self.best_iter[i][j] >= self.early_stopping_round:
                            ret = self.best_msg[i][j]
        msg = "\n".join(msg_lines)
        for i, j in met_pairs:
            self.best_msg[i][j] = msg
        if met_values:
            # latest eval values ride the next `quality` record too
            # (per-iteration eval metrics in the model-health timeline)
            self._last_metric_values = met_values
        if self.journal is not None and met_values:
            # metric values (train loss/AUC/...) in the same timeline as
            # the iteration records they describe
            self.journal.event("metrics", iteration=int(it),
                               values=met_values)
        return ret

    def get_eval_at(self, data_idx):
        """gbdt.cpp:352-373. 0 = train, i+1 = valid i."""
        out = []
        if data_idx == 0:
            for metric in self.training_metrics:
                out.extend(metric.eval(self.train_score_updater.host_score()))
        else:
            for metric in self.valid_metrics[data_idx - 1]:
                out.extend(metric.eval(self.valid_score_updaters[data_idx - 1].host_score()))
        if out:
            # latest eval values ride the next `quality` record (the
            # Python-API eval path; the CLI path lands here via
            # output_metric's own loop)
            prefix = "training" if data_idx == 0 else f"valid_{data_idx}"
            self._last_metric_values.update(
                {f"{prefix} {n}": float(v)
                 for n, v in zip(self.get_eval_names(data_idx), out)})
        return out

    def get_eval_names(self, data_idx):
        metrics = (self.training_metrics if data_idx == 0
                   else self.valid_metrics[data_idx - 1])
        names = []
        for m in metrics:
            names.extend(m.names)
        return names

    def get_predict_at(self, data_idx):
        """gbdt.cpp:381-419: transformed per-row predictions of a bound dataset."""
        if data_idx == 0:
            updater = self.train_score_updater
        else:
            updater = self.valid_score_updaters[data_idx - 1]
        raw = updater.host_score()
        n = updater.num_data
        if self.num_class > 1:
            mat = raw.reshape(self.num_class, n).T
            p = common.softmax(mat, axis=1)
            return p.T.reshape(-1)
        if self.sigmoid > 0:
            return 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * raw))
        return raw

    def get_training_score(self):
        return self.train_score_updater.host_score()

    # ------------------------------------------------------------ prediction
    def _num_used_models(self, num_iteration=-1):
        total = len(self.models)
        if num_iteration > 0:
            return min(num_iteration * self.num_class, total)
        if self.num_iteration_for_pred > 0 and not self.train_data:
            return min(self.num_iteration_for_pred * self.num_class, total)
        return total

    def _stacked_model_arrays(self, n_used):
        """Pad all trees' arrays to one (T, ...) tensor set so prediction
        traverses EVERY tree at once (the reference parallelizes file
        prediction across rows with OpenMP, predictor.hpp:82-130; here
        the tree axis is vectorized too). Cached per model-list state."""
        key = (n_used, len(self.models),
               getattr(self.models, "version", -1))
        cached = getattr(self, "_stack_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        trees = [self.models[i].materialize()
                 if hasattr(self.models[i], "materialize") else self.models[i]
                 for i in range(n_used)]
        max_l = max(t.num_leaves for t in trees)
        t_cnt = len(trees)
        sf = np.zeros((t_cnt, max(max_l - 1, 1)), np.int32)
        thr = np.zeros_like(sf, dtype=np.float64)
        dt = np.zeros_like(sf, dtype=np.int8)
        lc = np.full_like(sf, ~0)
        rc = np.full_like(sf, ~0)
        lv = np.zeros((t_cnt, max_l), np.float64)
        has_split = np.zeros(t_cnt, bool)
        depth = 1
        for i, t in enumerate(trees):
            ns = t.num_leaves - 1
            if ns > 0:
                sf[i, :ns] = t.split_feature_real
                thr[i, :ns] = t.threshold
                dt[i, :ns] = t.decision_type
                lc[i, :ns] = t.left_child
                rc[i, :ns] = t.right_child
                has_split[i] = True
                depth = max(depth, t.max_depth)
            lv[i, :t.num_leaves] = t.leaf_value
        stacked = (sf, thr, dt, lc, rc, lv, has_split, depth)
        self._stack_cache = (key, stacked)
        return stacked

    def _stacked_linear_arrays(self, n_used):
        """Per-leaf linear-model arrays stacked across the first n_used
        trees, or None when none is linear: (const (T, L) f64,
        coeff (T, L, C) f64, feat (T, L, C) int32 real column ids,
        cnt (T, L) int32) with L matching _stacked_model_arrays' leaf
        axis and C the widest leaf model in the ensemble. Constant
        leaves (and whole constant trees) carry cnt 0 and zero rows, so
        a fused serving kernel can branch per (row, tree) lane on
        cnt > 0 alone (serving/compiled_model.py)."""
        lin_idx = set(self._linear_model_indices(n_used))
        if not lin_idx:
            return None
        trees = [self.models[i].materialize()
                 if hasattr(self.models[i], "materialize")
                 else self.models[i] for i in range(n_used)]
        max_l = max(t.num_leaves for t in trees)
        width = max(t.leaf_coeff.shape[1] for i, t in enumerate(trees)
                    if i in lin_idx)
        const = np.zeros((n_used, max_l), np.float64)
        coeff = np.zeros((n_used, max_l, width), np.float64)
        feat = np.zeros((n_used, max_l, width), np.int32)
        cnt = np.zeros((n_used, max_l), np.int32)
        for i, t in enumerate(trees):
            if i not in lin_idx:
                continue
            nl, c = t.num_leaves, t.leaf_coeff.shape[1]
            const[i, :nl] = t.leaf_const
            coeff[i, :nl, :c] = t.leaf_coeff
            feat[i, :nl, :c] = t.leaf_coeff_feat
            cnt[i, :nl] = t.leaf_coeff_count
        return const, coeff, feat, cnt

    # rows*trees above this run the jitted device traversal (the
    # reference parallelizes prediction with OpenMP, predictor.hpp:82-130;
    # here rows AND trees vectorize on device, class reduction on the MXU).
    # Class-level defaults; `device_predict_cells` / `host_traverse_cells`
    # config knobs override per booster (reset_training_data), and the
    # `device_predict` knob / LIGHTGBM_TPU_DEVICE_PREDICT env flag force
    # the path outright (docs/Parameters.md).
    DEVICE_PREDICT_CELLS = 20_000_000
    # single-dispatch (lax.map) predict when the padded f32 input fits
    # this budget; beyond it, per-block dispatches bound device memory
    DEVICE_PREDICT_INPUT_MAX = 2 << 30
    _PREDICT_BLOCK = 65_536
    # host-path (rows x trees) cells per traversal block (peak memory)
    _HOST_TRAVERSE_CELLS = 4_000_000

    def _device_model(self, n_used):
        """Stacked tree arrays placed on device (f32/int32), cached per
        model-list state."""
        key = (n_used, len(self.models),
               getattr(self.models, "version", -1))
        cached = getattr(self, "_dev_model_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        sf, thr, dt, lc, rc, lv, has_split, depth = \
            self._stacked_model_arrays(n_used)
        # numeric thresholds are f64 on the host path; see
        # f32_safe_thresholds for the round-toward--inf cast contract
        thr32 = f32_safe_thresholds(thr, dt)
        dev = (jnp.asarray(sf), jnp.asarray(thr32, jnp.float32),
               jnp.asarray(dt == Tree.CATEGORICAL),
               jnp.asarray(lc), jnp.asarray(rc),
               jnp.asarray(lv, jnp.float32),
               jnp.asarray(np.where(has_split, 0, ~0).astype(np.int32)),
               int(depth))
        self._dev_model_cache = (key, dev)
        return dev

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(9,))
    def _predict_block_device(xb, sf, thr, cat, lc, rc, lv, node0,
                              cls_onehot, depth):
        """(B, F) raw f32 rows -> (B, K) class sums: the lockstep
        traversal (device_traverse; NaN routes right on BOTH numeric
        and categorical nodes, matching the host path), then the
        per-class reduction runs as a (B, T) x (T, K) matmul inside
        the same program (MXU)."""
        node = device_traverse(xb, sf, thr, cat, lc, rc, node0, depth)
        t_idx = jnp.arange(sf.shape[0])
        vals = lv[t_idx[None, :], ~node]                        # (B, T)
        # HIGHEST: the TPU's default f32 contraction rounds its
        # operands to bfloat16, which would cut every leaf value to 8
        # mantissa bits
        return jnp.dot(vals, cls_onehot,
                       precision=jax.lax.Precision.HIGHEST)     # (B, K)

    def _predict_raw_device(self, x, n_used):
        """Device batch prediction: fixed-size row blocks through ONE
        compiled traversal+reduction program. f32 thresholds/values —
        the host path remains the f64 reference for small batches."""
        sf, thr, cat, lc, rc, lv, node0, depth = self._device_model(n_used)
        t_cnt = sf.shape[0]
        cls_onehot = jnp.asarray(
            (np.arange(t_cnt)[:, None] % self.num_class
             == np.arange(self.num_class)[None, :]).astype(np.float32))
        n = x.shape[0]
        block = self._PREDICT_BLOCK
        nb = -(-n // block)
        # bucket the block count (round up to a multiple of the
        # 3rd-highest bit) so distinct batch sizes share O(log N)
        # compiled map shapes instead of one trace+compile per size.
        # Worst-case padding overhead ~12.5% of traversal compute.
        if nb > 4:
            step = 1 << max(nb.bit_length() - 3, 0)
            nb = -(-nb // step) * step
        f = x.shape[1]
        if nb > 1 and nb * block * f * 4 <= self.DEVICE_PREDICT_INPUT_MAX:
            # whole matrix in ONE dispatch: lax.map over row blocks
            # (one host round trip instead of one per block)
            xall = np.zeros((nb * block, f), dtype=np.float32)
            xall[:n] = x
            out = self._predict_map_device(
                jnp.asarray(xall).reshape(nb, block, f), sf, thr, cat,
                lc, rc, lv, node0, cls_onehot, depth)
            return np.asarray(out).reshape(nb * block, -1)[:n] \
                .astype(np.float64)
        outs = []
        for s in range(0, n, block):
            xb = np.asarray(x[s:s + block], dtype=np.float32)
            pad = block - xb.shape[0]
            if pad:
                xb = np.pad(xb, ((0, pad), (0, 0)))
            outs.append(self._predict_block_device(
                jnp.asarray(xb), sf, thr, cat, lc, rc, lv, node0,
                cls_onehot, depth))
        host = np.concatenate([np.asarray(o) for o in outs], axis=0)[:n]
        return host.astype(np.float64)

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(9,))
    def _predict_map_device(xblocks, sf, thr, cat, lc, rc, lv, node0,
                            cls_onehot, depth):
        """(NB, B, F) -> (NB, B, K): sequential lax.map over the same
        per-block traversal — one compiled program, one dispatch."""
        def one(xb):
            # nested jit traces inline
            return GBDT._predict_block_device(
                xb, sf, thr, cat, lc, rc, lv, node0, cls_onehot, depth)
        return jax.lax.map(one, xblocks)

    def predict_raw(self, x, num_iteration=-1):
        """Raw scores for (N, num_total_features) raw values -> (N, K).

        All trees traverse together: per depth step one (rows, trees)
        gather instead of a Python loop over trees. Large batches
        (rows x trees >= DEVICE_PREDICT_CELLS) run the jitted device
        traversal instead of the host loop."""
        x = np.atleast_2d(np.asarray(x, dtype=np.float64))
        n_used = self._num_used_models(num_iteration)
        n = x.shape[0]
        out = np.zeros((n, self.num_class))
        if n_used == 0 or n == 0:
            return out
        if self._use_device_predict(n, n_used):
            return self._predict_raw_device(x, n_used)
        lv = self._stacked_model_arrays(n_used)[5]
        lin_idx = self._linear_model_indices(n_used)
        t_cnt = lv.shape[0]
        t_idx = np.arange(t_cnt)
        cls = t_idx % self.num_class       # class-major model list
        block = max(1, min(n, self._HOST_TRAVERSE_CELLS // max(t_cnt, 1)))
        for s in range(0, n, block):
            xb = x[s:s + block]
            node = self._traverse_host(xb, n_used)               # (b, T)
            vals = lv[t_idx[None, :], ~node]                     # (b, T)
            # linear leaves: the gathered constant is exactly the
            # missing-value fallback, so overwrite in place per tree
            for i in lin_idx:
                vals[:, i] = self.models[i]._linear_values(
                    xb, (~node[:, i]).astype(np.int32), vals[:, i])
            for k in range(self.num_class):
                out[s:s + block, k] = vals[:, cls == k].sum(axis=1)
        return out

    def apply_predict_config(self, config):
        """Plumb the predict-routing knobs (docs/Parameters.md) onto
        this booster. Called from reset_training_data AND the predict-
        only CLI path (application.py init_predict), which loads models
        without ever training; class attrs remain the defaults for
        boosters that never saw a config."""
        self.DEVICE_PREDICT_CELLS = int(getattr(
            config, "device_predict_cells", self.DEVICE_PREDICT_CELLS))
        self._HOST_TRAVERSE_CELLS = int(getattr(
            config, "host_traverse_cells", self._HOST_TRAVERSE_CELLS))
        self.device_predict = str(getattr(config, "device_predict", "auto"))

    def _use_device_predict(self, n, n_used):
        """Route a predict_raw call host vs device. The env flag wins
        when set ("0"/"false" forces host, "force"/"true" forces
        device), else the `device_predict` config knob, else the
        cells-threshold auto rule (docs/Parameters.md).
        `force_host_predict` beats even the env: a booster serving as
        a PRECISION REFERENCE (serving/drift.py host_reference_scorer)
        must stay on the host f64 path no matter how the deployment
        tunes its own predictors."""
        if getattr(self, "force_host_predict", False):
            return False
        if self._linear_model_indices(n_used):
            # the training-side device traversal gathers CONSTANTS; the
            # fused traversal+dot kernels live in serving
            # (serving/compiled_model.py) — training predict stays on
            # the host f64 path for linear models, even under "force"
            return False
        knob = os.environ.get("LIGHTGBM_TPU_DEVICE_PREDICT")
        if knob in (None, "", "1"):  # "1" was the legacy auto default
            knob = str(getattr(self, "device_predict", "auto"))
        knob = knob.lower()
        if knob in ("0", "false", "off", "-"):
            return False
        if knob in ("force", "true", "+"):
            return True
        return n * n_used >= self.DEVICE_PREDICT_CELLS

    def _linear_model_indices(self, n_used):
        """Model-list indices of linear-leaf trees among the first
        n_used. LazyTree carries is_linear=False as a class attribute,
        so this probe never forces a materialization."""
        return [i for i in range(n_used)
                if getattr(self.models[i], "is_linear", False)]

    def _traverse_host(self, xb, n_used):
        """Host traversal of one row block through all stacked trees:
        returns the final (b, T) node states (~leaf encoded). Shared by
        predict_raw's host path and predict_leaf_index."""
        sf, thr, dt, lc, rc, lv, has_split, depth = \
            self._stacked_model_arrays(n_used)
        t_cnt = sf.shape[0]
        t_idx = np.arange(t_cnt)
        xbs = np.nan_to_num(xb)  # the int cast below needs a finite input
        node = np.where(has_split[None, :], 0, ~0).astype(np.int32)
        node = np.broadcast_to(node, (len(xb), t_cnt)).copy()
        for _ in range(depth):
            active = node >= 0
            if not active.any():
                break
            nd = np.maximum(node, 0)
            feat = sf[t_idx[None, :], nd]
            th = thr[t_idx[None, :], nd]
            d = dt[t_idx[None, :], nd]
            fval = xb[np.arange(len(xb))[:, None], feat]
            fcat = xbs[np.arange(len(xb))[:, None], feat]
            # NaN routes RIGHT on categorical nodes too (a missing value
            # is not a category id; reference default-direction
            # semantics) — numeric NaN already goes right via <= False
            go_left = np.where(d == Tree.CATEGORICAL,
                               (fcat.astype(np.int64) == th.astype(np.int64))
                               & ~np.isnan(fval),
                               fval <= th)
            nxt = np.where(go_left, lc[t_idx[None, :], nd],
                           rc[t_idx[None, :], nd])
            node = np.where(active, nxt, node)
        return node

    def predict(self, x, num_iteration=-1):
        """gbdt.cpp:622-636: sigmoid/softmax-transformed predictions."""
        raw = self.predict_raw(x, num_iteration)
        if self.sigmoid > 0 and self.num_class == 1:
            return 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * raw))
        if self.num_class > 1:
            return common.softmax(raw, axis=1)
        return raw

    def predict_leaf_index(self, x, num_iteration=-1):
        """(N, T) leaf indices via the same all-trees host traversal as
        predict_raw (the reference runs this OpenMP-parallel per row,
        predictor.hpp:108-118)."""
        x = np.atleast_2d(np.asarray(x, dtype=np.float64))
        n_used = self._num_used_models(num_iteration)
        n = x.shape[0]
        if n_used == 0 or n == 0:
            # (N, T) even when empty: vstacking chunked calls must work
            return np.zeros((n, n_used), dtype=np.int32)
        block = max(1, min(n, self._HOST_TRAVERSE_CELLS // n_used))
        outs = []
        for s in range(0, n, block):
            node = self._traverse_host(x[s:s + block], n_used)
            outs.append((~node).astype(np.int32))
        return np.concatenate(outs, axis=0)

    # --------------------------------------------------------- serialization
    def feature_importance_values(self, importance_type="split"):
        """Reference-semantics importance vector over the model list
        (telemetry/quality.py — the ONE aggregation every consumer
        shares): int64 split counts or float64 gain sums, length
        max_feature_idx + 1."""
        from ..telemetry.quality import feature_importance_from_models
        return feature_importance_from_models(
            self.models, self.max_feature_idx + 1, importance_type)

    def feature_importance(self):
        """Split-count importance pairs for the model file's
        "feature importances:" block (gbdt.cpp:585-610)."""
        imp = self.feature_importance_values("split")
        pairs = [(int(imp[i]), self.feature_names[i] if i < len(self.feature_names)
                  else f"Column_{i}") for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        return pairs

    def save_model_to_string(self, num_iteration=-1):
        """gbdt.cpp:468-513 text format.

        Models with linear leaves declare `format_version=2` right
        after the name line (MODEL_FORMAT_VERSION); constant-leaf
        models omit the line entirely so their output stays
        byte-identical to every pre-linear reader and writer."""
        n_used = len(self.models) if num_iteration <= 0 else min(
            num_iteration * self.num_class, len(self.models))
        lines = [self.name]
        if any(getattr(self.models[i], "is_linear", False)
               for i in range(n_used)):
            lines.append(f"format_version={MODEL_FORMAT_VERSION}")
        lines += [f"num_class={self.num_class}",
                  f"label_index={self.label_idx}",
                  f"max_feature_idx={self.max_feature_idx}"]
        if self.objective is not None:
            lines.append(f"objective={self.objective.name}")
        elif getattr(self, "_loaded_objective_name", ""):
            # a loaded booster has no live objective; keep the declared
            # name so save(load(s)) round-trips byte-identically
            lines.append(f"objective={self._loaded_objective_name}")
        lines.append(f"sigmoid={self.sigmoid:g}")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("")
        for i in range(n_used):
            lines.append(f"Tree={i}")
            lines.append(self.models[i].to_string())
        lines.append("")
        lines.append("feature importances:")
        for cnt, fname in self.feature_importance():
            lines.append(f"{fname}={cnt}")
        return "\n".join(lines) + "\n"

    def save_model_to_file(self, num_iteration, filename):
        # crash-atomic: a kill mid-save must never leave a truncated
        # model where a valid one stood (utils/checkpoint.py)
        from ..utils.checkpoint import atomic_write_text
        atomic_write_text(filename, self.save_model_to_string(num_iteration))
        if self.dataset_profile is not None:
            # the training-time baseline distribution travels with the
            # model: <model>.profile.json is what the serving drift
            # monitor loads (io/profile.py, serving/drift.py)
            from ..io.profile import model_profile_path
            try:
                self.dataset_profile.save(model_profile_path(filename))
            except OSError as e:
                Log.warning("could not write dataset profile next to "
                            "%s: %s", filename, e)

    def load_model_from_string(self, model_str):
        """gbdt.cpp:515-583."""
        self.models = _VersionedList()
        lines = model_str.split("\n")

        def find_line(prefix):
            for ln in lines:
                if prefix in ln:
                    return ln
            return ""

        line = find_line("format_version=")
        fmt = int(line.split("=")[1]) if line else 1
        if fmt > MODEL_FORMAT_VERSION:
            Log.fatal("model declares format_version=%d but this reader "
                      "supports versions <= %d — load it with the "
                      "lightgbm_tpu release that wrote it", fmt,
                      MODEL_FORMAT_VERSION)
        line = find_line("num_class=")
        if not line:
            Log.fatal("Model file doesn't specify the number of classes")
        self.num_class = int(line.split("=")[1])
        line = find_line("label_index=")
        if not line:
            Log.fatal("Model file doesn't specify the label index")
        self.label_idx = int(line.split("=")[1])
        line = find_line("max_feature_idx=")
        if not line:
            Log.fatal("Model file doesn't specify max_feature_idx")
        self.max_feature_idx = int(line.split("=")[1])
        line = find_line("objective=")
        self._loaded_objective_name = (line.split("=", 1)[1].strip()
                                       if line else "")
        line = find_line("sigmoid=")
        self.sigmoid = float(line.split("=")[1]) if line else -1.0
        line = find_line("feature_names=")
        if not line:
            Log.fatal("Model file doesn't contain feature names")
        self.feature_names = line.split("=", 1)[1].split(" ")
        if len(self.feature_names) != self.max_feature_idx + 1:
            Log.fatal("Wrong size of feature_names")

        i = 0
        while i < len(lines):
            if lines[i].startswith("Tree="):
                i += 1
                start = i
                while i < len(lines) and not lines[i].startswith("Tree="):
                    if lines[i].startswith("feature importances:"):
                        break
                    i += 1
                self.models.append(Tree.from_string(
                    "\n".join(lines[start:i]), format_version=fmt))
            else:
                i += 1
        Log.info("Finished loading %d models", len(self.models))
        self.num_iteration_for_pred = len(self.models) // max(self.num_class, 1)
        self.num_init_iteration = self.num_iteration_for_pred

    def dump_model(self):
        """JSON dump (gbdt.cpp:431-466)."""
        out = ["{"]
        out.append(f'"name":"{self.name}",')
        out.append(f'"num_class":{self.num_class},')
        out.append(f'"label_index":{self.label_idx},')
        out.append(f'"max_feature_idx":{self.max_feature_idx},')
        out.append(f'"sigmoid":{self.sigmoid:g},')
        names = '","'.join(self.feature_names)
        out.append(f'"feature_names":["{names}"],')
        tree_parts = []
        for i, tree in enumerate(self.models):
            tree_parts.append('{' + f'"tree_index":{i},' + tree.to_json() + '}')
        out.append('"tree_info":[' + ",".join(tree_parts) + "]")
        out.append("}")
        return "\n".join(out) + "\n"

    def merge_from(self, other):
        """Booster merge for continued training (gbdt.h:44-61)."""
        self.models = _VersionedList(list(other.models) + self.models)
        self.num_init_iteration += len(other.models) // max(self.num_class, 1)

    # -------------------------------------------------------- checkpointing
    def _rng_registry(self):
        """Named stateful HOST RNGs that must survive a resume for
        bit-identical continuation. Device sampling (bagging, GOSS) is
        stateless — keyed on the iteration index — so only the numpy
        streams need capturing: the feature sampler, and DART's drop
        sampler when present."""
        regs = {}
        learner = self.tree_learner
        if learner is not None and getattr(learner, "random", None) is not None:
            regs["feature_sampler"] = learner.random
        if getattr(self, "_random_for_drop", None) is not None:
            regs["drop_sampler"] = self._random_for_drop
        return regs

    def _multihost_row_sharded(self):
        """True when training rows are partitioned across processes —
        the layout under which each rank's train score covers only its
        local block (parallel/learners.py)."""
        learner = self.tree_learner
        return (learner is not None
                and getattr(learner, "n_proc", 1) > 1
                and getattr(learner, "shard_rows", False))

    def _allgather_row_counts(self):
        """(P,) local-row counts in rank order. COLLECTIVE: every
        process must call this at the same point (watchdog-armed — a
        peer wedged at a snapshot point must not hang the others
        forever)."""
        from jax.experimental import multihost_utils
        n_local = int(np.asarray(self.train_score_updater.score).shape[-1])
        with heartbeat.collective_guard("snapshot_counts_gather"):
            return np.asarray(multihost_utils.process_allgather(
                np.asarray([n_local], dtype=np.int64))).reshape(-1)

    def _gather_global_train_score(self):
        """Assemble the GLOBAL (num_class, N) train score from every
        rank's local block (ranks hold contiguous row ranges in rank
        order, parallel/distributed.py partition_rows). COLLECTIVE —
        which is why multi-host snapshots require every rank to call
        capture_training_state at the cadence point even though only
        rank 0 writes the file (application.py train): a rank-local
        snapshot would be useless to a restart whose surviving ranks
        re-partition the rows (the shrunken-world resume path)."""
        from jax.experimental import multihost_utils
        local = np.asarray(self.train_score_updater.score,
                           dtype=np.float32)            # (K, n_local)
        counts = self._allgather_row_counts()
        n_max = int(counts.max())
        padded = np.zeros((local.shape[0], n_max), dtype=np.float32)
        padded[:, :local.shape[1]] = local
        with heartbeat.collective_guard("snapshot_score_gather"):
            blocks = np.asarray(multihost_utils.process_allgather(padded))
        return np.concatenate(
            [blocks[r][:, :int(counts[r])] for r in range(len(counts))],
            axis=1)

    def capture_training_state(self):
        """Full mid-training state for utils/checkpoint.py: everything
        `restore_training_state` needs to continue training on the SAME
        config + dataset and produce the bit-identical model string of
        an uninterrupted run. Score arrays are saved verbatim (float32
        bits) — recomputing them from trees would change summation
        order and diverge the histogram sums. Multi-host row-sharded
        training stores the allgathered GLOBAL score with a layout tag,
        so a restart can re-slice it for any surviving topology."""
        if self._multihost_row_sharded():
            train_score = self._gather_global_train_score()
            score_layout = "global_rows"
        else:
            train_score = np.asarray(self.train_score_updater.score)
            score_layout = "local"
        state = {
            "state_version": 1,
            "model_str": self.save_model_to_string(-1),
            "iter": int(self.iter),
            "num_init_iteration": int(self.num_init_iteration),
            "num_class": int(self.num_class),
            "train_score": train_score,
            "train_score_layout": score_layout,
            "valid_scores": [np.asarray(u.score)
                             for u in self.valid_score_updaters],
            "best_iter": [list(map(int, x)) for x in self.best_iter],
            "best_score": [list(map(float, x)) for x in self.best_score],
            "best_msg": [list(x) for x in self.best_msg],
        }
        for name, rng in self._rng_registry().items():
            algo, keys, pos, has_gauss, cached = rng._rng.get_state()
            state[f"rng_{name}"] = {"algo": algo, "pos": int(pos),
                                    "has_gauss": int(has_gauss),
                                    "cached": float(cached)}
            state[f"rng_{name}_keys"] = np.asarray(keys)
        # bin-space split encoding: the model TEXT stores real-valued
        # thresholds only, but continued training re-scores restored
        # trees in bin space (DART's drop/normalize, early-stopping
        # truncation) — so the in-bin arrays ride along, concatenated
        # across trees
        n_splits, tib, sfi = [], [], []
        lin_counts, lin_feats = [], []
        for model in self.models:
            tree = (model.materialize() if hasattr(model, "materialize")
                    else model)
            ns = tree.num_leaves - 1
            n_splits.append(ns)
            if ns > 0:
                tib.append(np.asarray(tree.threshold_in_bin[:ns], np.int32))
                sfi.append(np.asarray(tree.split_feature[:ns], np.int32))
            # linear leaves also need their INNER coefficient feature
            # ids for bin-space re-scoring after resume (the text
            # format stores real column ids only): per-leaf counts +
            # flattened inner ids, concatenated across trees
            if getattr(tree, "is_linear", False):
                cnts = np.asarray(tree.leaf_coeff_count, np.int32)
                lin_counts.append(cnts)
                lin_feats.append(np.concatenate(
                    [tree.leaf_coeff_feat_inner[leaf, :cnts[leaf]]
                     for leaf in range(tree.num_leaves)]
                    or [np.zeros(0, np.int32)]).astype(np.int32))
            else:
                lin_counts.append(np.zeros(ns + 1, np.int32))
                lin_feats.append(np.zeros(0, np.int32))
        state["tree_n_splits"] = np.asarray(n_splits, np.int32)
        state["tree_threshold_in_bin"] = (
            np.concatenate(tib) if tib else np.zeros(0, np.int32))
        state["tree_split_feature_inner"] = (
            np.concatenate(sfi) if sfi else np.zeros(0, np.int32))
        state["tree_leaf_coeff_counts"] = (
            np.concatenate(lin_counts) if lin_counts
            else np.zeros(0, np.int32))
        state["tree_leaf_feat_inner"] = (
            np.concatenate(lin_feats) if lin_feats
            else np.zeros(0, np.int32))
        return state

    def restore_training_state(self, state):
        """Inverse of `capture_training_state`, applied to a freshly
        initialized booster bound to the same config/datasets."""
        if int(state.get("state_version", 0)) != 1:
            Log.fatal("Unsupported checkpoint state version %s",
                      state.get("state_version"))
        if int(state["num_class"]) != self.num_class:
            Log.fatal("Checkpoint num_class %d does not match booster "
                      "num_class %d", int(state["num_class"]), self.num_class)
        n_valid = len(state.get("valid_scores", []))
        if n_valid != len(self.valid_score_updaters):
            Log.fatal("Checkpoint has %d valid-set scores but booster has "
                      "%d valid sets bound", n_valid,
                      len(self.valid_score_updaters))
        self.load_model_from_string(state["model_str"])
        # re-attach the bin-space split encoding the text format drops
        # (see capture_training_state)
        n_splits = np.asarray(state.get("tree_n_splits", []), np.int32)
        if len(n_splits) == len(self.models):
            offsets = np.concatenate([[0], np.cumsum(n_splits)])
            tib = np.asarray(state["tree_threshold_in_bin"], np.int32)
            sfi = np.asarray(state["tree_split_feature_inner"], np.int32)
            lin_counts = np.asarray(
                state.get("tree_leaf_coeff_counts", []), np.int32)
            lin_feats = np.asarray(
                state.get("tree_leaf_feat_inner", []), np.int32)
            leaf_off = np.concatenate([[0], np.cumsum(n_splits + 1)])
            feat_pos = 0
            for idx, tree in enumerate(self.models):
                lo, hi = offsets[idx], offsets[idx + 1]
                if hi > lo:
                    tree.threshold_in_bin = tib[lo:hi].copy()
                    tree.split_feature = sfi[lo:hi].copy()
                if len(lin_counts) != leaf_off[-1]:
                    continue  # pre-linear checkpoint (no linear trees)
                cnts = lin_counts[leaf_off[idx]:leaf_off[idx + 1]]
                if getattr(tree, "is_linear", False):
                    for leaf in range(tree.num_leaves):
                        k = int(cnts[leaf])
                        tree.leaf_coeff_feat_inner[leaf, :k] = \
                            lin_feats[feat_pos:feat_pos + k]
                        feat_pos += k
                else:
                    feat_pos += int(cnts.sum())
        # load_model_from_string prepares for PREDICTION (treats every
        # tree as an init tree); a resume continues TRAINING, so the
        # split between init trees and this run's own is the captured one
        self.num_init_iteration = int(state["num_init_iteration"])
        self.num_iteration_for_pred = 0
        self.iter = int(state["iter"])
        train_score = np.asarray(state["train_score"], dtype=np.float32)
        if (state.get("train_score_layout") == "global_rows"
                and self._multihost_row_sharded()):
            # global capture -> this topology's local block: contiguous
            # rank-order slices, valid for the ORIGINAL topology and for
            # a shrunken world that re-partitioned the rows. (On a
            # single process the global score IS the local score and
            # the plain shape check below covers it.)
            counts = self._allgather_row_counts()
            if int(counts.sum()) != train_score.shape[-1]:
                Log.fatal("Checkpoint global train score has %d rows "
                          "but the current topology holds %d "
                          "(different training data?)",
                          train_score.shape[-1], int(counts.sum()))
            rank = jax.process_index()
            offset = int(counts[:rank].sum())
            train_score = train_score[:, offset:offset + int(counts[rank])]
        if train_score.shape != tuple(self.train_score_updater.score.shape):
            Log.fatal("Checkpoint train-score shape %s does not match "
                      "dataset shape %s (different training data?)",
                      train_score.shape,
                      tuple(self.train_score_updater.score.shape))
        self.train_score_updater.score = jnp.asarray(train_score)
        for updater, score in zip(self.valid_score_updaters,
                                  state["valid_scores"]):
            updater.score = jnp.asarray(np.asarray(score, dtype=np.float32))
        self.best_iter = [list(x) for x in state.get("best_iter", [])]
        self.best_score = [list(x) for x in state.get("best_score", [])]
        self.best_msg = [list(x) for x in state.get("best_msg", [])]
        for name, rng in self._rng_registry().items():
            meta = state.get(f"rng_{name}")
            keys = state.get(f"rng_{name}_keys")
            if meta is None or keys is None:
                continue
            rng._rng.set_state((meta["algo"],
                                np.asarray(keys, dtype=np.uint32),
                                int(meta["pos"]), int(meta["has_gauss"]),
                                float(meta["cached"])))
        # bag cache and prediction caches may describe pre-restore state
        self._bag_rows = None
        self._bag_window = None
        self._stack_cache = None
        self._dev_model_cache = None
        Log.info("Restored training state at iteration %d (%d trees)",
                 self.iter, len(self.models))


def create_boosting(boosting_type, input_model=""):
    """Factory + model-file type sniffing (src/boosting/boosting.cpp:7-66).
    "goss" is a post-reference extension (models/goss.py)."""
    from .dart import DART
    from .goss import GOSS
    if input_model:
        with open(input_model) as f:
            first = f.readline().strip()
        boosting_type = (first if first in ("gbdt", "dart", "goss")
                         else boosting_type)
    if boosting_type == "gbdt":
        return GBDT()
    if boosting_type == "dart":
        return DART()
    if boosting_type == "goss":
        return GOSS()
    Log.fatal("Unknown boosting type %s", boosting_type)
