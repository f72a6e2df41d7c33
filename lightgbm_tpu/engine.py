"""Training entry points: `train` and `cv`.

Reference: python-package/lightgbm/engine.py:12-395. Same control flow:
predictor chaining for init_model, valid-set reference alignment,
callback orchestration (before/after each iteration, ordered), early
stopping via EarlyStopException, and n-fold CV built on Dataset.subset
with mean/std aggregation.
"""

import collections
from operator import attrgetter

import numpy as np

from . import callback
from .basic import Booster, Dataset, LightGBMError, _InnerPredictor, is_str


def _configure_callbacks(callbacks):
    """Normalize user callbacks: default ordering, split into before/after
    iteration groups, sorted by `.order` (engine.py:124-150)."""
    if callbacks is None:
        callbacks = set()
    else:
        for i, cb in enumerate(callbacks):
            cb.__dict__.setdefault("order", i - len(callbacks))
        callbacks = set(callbacks)
    return callbacks


def _split_callbacks(callbacks):
    before = {cb for cb in callbacks if getattr(cb, "before_iteration", False)}
    after = callbacks - before
    return (sorted(before, key=attrgetter("order")),
            sorted(after, key=attrgetter("order")))


def _train_blockwise(booster, callbacks_after_iter, init_iteration,
                     num_boost_round, is_valid_contain_train, feval,
                     early_stopping_rounds, ckpt_cbs=(), start_offset=0):
    """Fused multi-iteration training with per-iteration callback
    replay (see the blockwise comment in train()). Each block is ONE
    device program (gbdt.train_many_eval); metric values for every
    iteration inside the block come from device-computed score
    snapshots. An early-stop break mid-block drops the overshoot
    trees scorelessly — the snapshot already IS the kept state.

    Checkpoint callbacks (`ckpt_cbs`) fire only at BLOCK boundaries:
    mid-block the model list already holds the whole block's trees, so
    a mid-block snapshot would capture the future. The block size is
    clamped (and boundaries aligned) to the snapshot cadence so every
    cadence point is a block boundary."""
    gbdt = booster.gbdt
    end = init_iteration + num_boost_round
    # overshoot past the true stopping round costs at most block-1
    # wasted iterations, so tie the block to the early-stop patience
    if early_stopping_rounds is None:
        block_full = num_boost_round
    else:
        block_full = min(num_boost_round,
                         max(5, min(int(early_stopping_rounds), 25)))
    snap_period = min((cb.period for cb in ckpt_cbs if cb.period > 0),
                      default=0)
    if snap_period:
        block_full = max(1, min(block_full, snap_period))

    def fire_checkpoints(i):
        for cb in ckpt_cbs:
            cb(callback.CallbackEnv(
                model=booster, cvfolds=None, iteration=i,
                begin_iteration=init_iteration, end_iteration=end,
                evaluation_result_list=[]))

    def run_callbacks(i):
        """One iteration's eval + after-iteration callbacks against the
        CURRENT scores. Returns True on EarlyStopException."""
        evaluation_result_list = []
        if is_valid_contain_train:
            evaluation_result_list.extend(booster.eval_train(feval))
        evaluation_result_list.extend(booster.eval_valid(feval))
        try:
            for cb in callbacks_after_iter:
                cb(callback.CallbackEnv(
                    model=booster, cvfolds=None, iteration=i,
                    begin_iteration=init_iteration, end_iteration=end,
                    evaluation_result_list=evaluation_result_list))
        except callback.EarlyStopException:
            return True
        return False

    i = init_iteration + start_offset
    while i < end:
        step = min(block_full, end - i)
        if snap_period:
            # align boundaries to the cadence (a resume can start the
            # loop off-cadence only if the newest snapshot did)
            boundary = ((gbdt.iter // snap_period) + 1) * snap_period
            step = min(step, max(1, boundary - gbdt.iter))
        t_eff, snap = gbdt.train_many_eval(step)
        for t in range(t_eff):
            snap.set_scores_at(t, with_train=is_valid_contain_train)
            if run_callbacks(i + t):
                snap.set_scores_at(t, with_train=True)
                snap.drop_tail_to(t)
                return
        if snap.finalize():
            # natural stop (an empty tree mid-block). The per-iteration
            # path this replay must match does NOT end here: the
            # reference python API ignores update()'s is-finished flag
            # and keeps calling it — evals repeat, and per-iteration
            # sampling (or multiclass gradient coupling) can resume
            # real splitting. First replay the stop iteration's
            # callbacks (it kept no tree: the scores are those of the
            # iteration before), then hand the remaining rounds to the
            # true per-iteration loop.
            i += t_eff
            if i < end and run_callbacks(i):
                return
            i += 1
            while i < end:
                booster.update()
                if run_callbacks(i):
                    return
                i += 1
            return
        i += t_eff
        fire_checkpoints(i - 1)


def train(params, train_set, num_boost_round=100,
          valid_sets=None, valid_names=None,
          fobj=None, feval=None, init_model=None,
          feature_name=None, categorical_feature=None,
          early_stopping_rounds=None, evals_result=None,
          verbose_eval=True, learning_rates=None, callbacks=None,
          resume_from=None):
    """Train one booster (engine.py:12-191). Returns the Booster with
    `best_iteration` set when early stopping fired.

    resume_from: a checkpoint directory (or CheckpointManager) written
    by `callback.checkpoint(...)`. When it holds a valid snapshot, full
    training state (trees, scores, sampling RNG, early-stop trackers,
    eval history) is restored and the loop continues from the
    snapshot's iteration — producing the bit-identical model string of
    an uninterrupted run with the same params and data. No valid
    snapshot = a normal cold start."""
    if is_str(init_model):
        predictor = _InnerPredictor(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model._to_predictor()
    else:
        predictor = None
    init_iteration = predictor.num_total_iteration if predictor is not None else 0

    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    train_set._set_predictor(predictor)
    train_set.set_feature_name(feature_name)
    train_set.set_categorical_feature(categorical_feature)

    is_valid_contain_train = False
    train_data_name = "training"
    reduced_valid_sets = []
    name_valid_sets = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, valid_data in enumerate(valid_sets):
            if valid_data is train_set:
                is_valid_contain_train = True
                if valid_names is not None and len(valid_names) > i:
                    train_data_name = valid_names[i]
                continue
            if not isinstance(valid_data, Dataset):
                raise TypeError("Training only accepts Dataset object")
            valid_data.set_reference(train_set)
            reduced_valid_sets.append(valid_data)
            if valid_names is not None and len(valid_names) > i:
                name_valid_sets.append(valid_names[i])
            else:
                name_valid_sets.append("valid_" + str(i))

    callbacks = _configure_callbacks(callbacks)
    default_print_cb = early_stop_cb = record_cb = None
    if verbose_eval is True:
        default_print_cb = callback.print_evaluation()
        callbacks.add(default_print_cb)
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool):
        default_print_cb = callback.print_evaluation(verbose_eval)
        callbacks.add(default_print_cb)
    if early_stopping_rounds is not None:
        early_stop_cb = callback.early_stopping(
            early_stopping_rounds, verbose=bool(verbose_eval))
        callbacks.add(early_stop_cb)
    if learning_rates is not None:
        callbacks.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        record_cb = callback.record_evaluation(evals_result)
        callbacks.add(record_cb)
    callbacks_before_iter, callbacks_after_iter = _split_callbacks(callbacks)

    booster = Booster(params=params, train_set=train_set)
    # late-bind the supervisor heartbeat's progress source: an embedder
    # that enabled heartbeats (parallel/heartbeat.py configure) gets
    # per-iteration liveness from this booster; no-op otherwise. Weakly
    # referenced: the process-lifetime service must not keep a dropped
    # booster (dataset bins, score arrays) alive after train() returns.
    import weakref
    from .parallel import heartbeat
    gbdt_ref = weakref.ref(booster.gbdt)

    def _iteration_source():
        gbdt = gbdt_ref()
        return gbdt.iter if gbdt is not None else -1

    heartbeat.bind_iteration_source(_iteration_source)
    if is_valid_contain_train:
        booster.set_train_data_name(train_data_name)
    for valid_set, name_valid_set in zip(reduced_valid_sets, name_valid_sets):
        booster.add_valid(valid_set, name_valid_set)

    all_cbs = callbacks_before_iter + callbacks_after_iter
    ckpt_cbs = [cb for cb in callbacks_after_iter
                if getattr(cb, "is_checkpoint", False)]
    for cb in ckpt_cbs:
        cb.bind_peers(all_cbs)
    # resume: restore the newest valid snapshot (trees, score arrays,
    # RNG streams, callback state) and skip the already-trained rounds
    start_offset = 0
    if resume_from is not None:
        from .utils.checkpoint import CheckpointManager
        manager = (resume_from if isinstance(resume_from, CheckpointManager)
                   else CheckpointManager(resume_from))
        state, _ = manager.load_latest()
        if state is not None:
            restorer = ckpt_cbs[0] if ckpt_cbs \
                else callback._Checkpoint(manager, 0)
            restorer.restore_into(booster, state, all_cbs)
            start_offset = min(booster.gbdt.iter, num_boost_round)
            if booster.gbdt.journal is not None:
                # the restart lands in the run journal's timeline next
                # to the abort that caused it (docs/Observability.md)
                booster.gbdt.journal.event(
                    "resume", iteration=int(booster.gbdt.iter))

    # fast path: nothing needs the per-round boundary (no callbacks, no
    # custom objective, no valid evaluation) — run the whole block as
    # the fused device scan (gbdt.train_many); semantics are identical
    # (parity pinned by tests/test_core_training.py and the fused GOSS/
    # bagging tests). The default print_evaluation callback is exempt:
    # with no valid sets its evaluation list is always empty and it
    # prints nothing (callback.py). Checkpoint callbacks are exempt
    # too: the scan is chopped into cadence-sized blocks with a
    # snapshot between blocks (same trees — block size only moves the
    # host-sync points).
    effective_after = [cb for cb in callbacks_after_iter
                       if cb is not default_print_cb and cb not in ckpt_cbs]
    if (not callbacks_before_iter and not effective_after
            and fobj is None and valid_sets is None
            and getattr(booster.gbdt, "_fused_eligible", lambda: False)()):
        periods = [cb.period for cb in ckpt_cbs if cb.period > 0]
        if periods:
            block = min(periods)
            stopped = False
            while booster.gbdt.iter < num_boost_round and not stopped:
                # align block boundaries to the cadence (a resume can
                # start off-cadence; fixed-size steps would then never
                # land on a snapshot point again)
                boundary = ((booster.gbdt.iter // block) + 1) * block
                step = min(boundary - booster.gbdt.iter,
                           num_boost_round - booster.gbdt.iter)
                stopped = booster.gbdt.train_many(step)
                for cb in ckpt_cbs:
                    cb(callback.CallbackEnv(
                        model=booster, cvfolds=None,
                        iteration=init_iteration + booster.gbdt.iter - 1,
                        begin_iteration=init_iteration,
                        end_iteration=init_iteration + num_boost_round,
                        evaluation_result_list=[]))
        elif num_boost_round > start_offset:
            booster.gbdt.train_many(num_boost_round - start_offset)
        booster.best_iteration = num_boost_round
        return booster

    # blockwise fused path (valid sets and/or early stopping present):
    # every callback here is one this function itself created from a
    # kwarg, so the per-iteration callback protocol can be REPLAYED
    # after a fused multi-iteration device block from per-iteration
    # score snapshots (gbdt.train_many_eval) — observable behavior
    # (eval values, print cadence, evals_result history, early-stop
    # round, final model) is identical to the per-iteration loop, but
    # tree building never leaves the device mid-block. Custom user
    # callbacks fall back to the true per-iteration loop: they may
    # mutate the booster mid-training.
    engine_created = {cb for cb in (default_print_cb, early_stop_cb,
                                    record_cb) if cb is not None}
    use_blockwise = (
        valid_sets is not None
        and fobj is None
        and not callbacks_before_iter
        and all(cb in engine_created or cb in ckpt_cbs
                for cb in callbacks_after_iter)
        and getattr(booster.gbdt, "_fused_eligible", lambda **_: False)(
            ignore_train_metrics=True))
    if use_blockwise:
        replay_after = [cb for cb in callbacks_after_iter
                        if cb not in ckpt_cbs]
        _train_blockwise(booster, replay_after, init_iteration,
                         num_boost_round, is_valid_contain_train, feval,
                         early_stopping_rounds, ckpt_cbs=ckpt_cbs,
                         start_offset=start_offset)
    else:
        for i in range(init_iteration + start_offset,
                       init_iteration + num_boost_round):
            for cb in callbacks_before_iter:
                cb(callback.CallbackEnv(model=booster, cvfolds=None, iteration=i,
                                        begin_iteration=init_iteration,
                                        end_iteration=init_iteration + num_boost_round,
                                        evaluation_result_list=None))
            booster.update(fobj=fobj)

            evaluation_result_list = []
            if valid_sets is not None:
                if is_valid_contain_train:
                    evaluation_result_list.extend(booster.eval_train(feval))
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after_iter:
                    cb(callback.CallbackEnv(model=booster, cvfolds=None, iteration=i,
                                            begin_iteration=init_iteration,
                                            end_iteration=init_iteration + num_boost_round,
                                            evaluation_result_list=evaluation_result_list))
            except callback.EarlyStopException:
                break
    if booster.attr("best_iteration") is not None:
        booster.best_iteration = int(booster.attr("best_iteration")) + 1
    else:
        # reference quirk kept (engine.py:190): without early stopping this
        # is num_boost_round, NOT init_iteration + num_boost_round — under
        # continued training predict(best_iteration) then truncates
        booster.best_iteration = num_boost_round
    return booster


class CVBooster:
    """One fold of CV (engine.py:194-209)."""

    def __init__(self, train_set, valid_test, params):
        self.train_set = train_set
        self.valid_test = valid_test
        self.booster = Booster(params=params, train_set=train_set)
        self.booster.add_valid(valid_test, "valid")

    def update(self, fobj):
        self.booster.update(fobj=fobj)

    def eval(self, feval):
        return self.booster.eval_valid(feval)


def _make_n_folds(full_data, nfold, params, seed, fpreproc=None,
                  stratified=False, shuffle=True):
    """engine.py:221-249."""
    np.random.seed(seed)
    if stratified:
        try:
            from sklearn.model_selection import StratifiedKFold
        except ImportError:
            raise LightGBMError("Scikit-learn is required for stratified cv")
        sfk = StratifiedKFold(n_splits=nfold, shuffle=shuffle, random_state=seed)
        idset = [x[1] for x in sfk.split(X=full_data.get_label(),
                                         y=full_data.get_label())]
    else:
        full_data.construct()
        n = full_data.num_data()
        randidx = np.random.permutation(n) if shuffle else np.arange(n)
        # reference quirk kept (engine.py:236-237): the last n % nfold rows
        # of the permutation appear in no fold
        kstep = int(len(randidx) / nfold)
        idset = [randidx[(i * kstep): min(len(randidx), (i + 1) * kstep)]
                 for i in range(nfold)]

    ret = []
    for k in range(nfold):
        train_set = full_data.subset(
            np.concatenate([idset[i] for i in range(nfold) if k != i]))
        valid_set = full_data.subset(idset[k])
        if fpreproc is not None:
            train_set, valid_set, tparam = fpreproc(train_set, valid_set,
                                                    params.copy())
        else:
            tparam = params
        ret.append(CVBooster(train_set, valid_set, tparam))
    return ret


def _agg_cv_result(raw_results):
    """engine.py:251-261."""
    cvmap = collections.defaultdict(list)
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            metric_type[one_line[1]] = one_line[3]
            cvmap[one_line[1]].append(one_line[2])
    return [("cv_agg", k, np.mean(v), metric_type[k], np.std(v))
            for k, v in cvmap.items()]


def cv(params, train_set, num_boost_round=10, nfold=5, stratified=False,
       shuffle=True, metrics=None, fobj=None, feval=None, init_model=None,
       feature_name=None, categorical_feature=None,
       early_stopping_rounds=None, fpreproc=None,
       verbose_eval=None, show_stdv=True, seed=0, callbacks=None):
    """Cross-validation (engine.py:263-395). Returns a dict
    {metric-mean: [...], metric-stdv: [...]}."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")

    if is_str(init_model):
        predictor = _InnerPredictor(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model._to_predictor()
    else:
        predictor = None
    train_set._set_predictor(predictor)
    train_set.set_feature_name(feature_name)
    train_set.set_categorical_feature(categorical_feature)

    params = dict(params)
    if metrics:
        existing = params.get("metric", []) or []
        metric_list = existing.split(",") if is_str(existing) else list(existing)
        if is_str(metrics):
            metric_list.append(metrics)
        else:
            metric_list.extend(metrics)
        params["metric"] = metric_list

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, nfold, params, seed, fpreproc,
                            stratified, shuffle)

    callbacks = _configure_callbacks(callbacks)
    if early_stopping_rounds is not None:
        callbacks.add(callback.early_stopping(early_stopping_rounds,
                                              verbose=False))
    if verbose_eval is True:
        callbacks.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool):
        callbacks.add(callback.print_evaluation(verbose_eval,
                                                show_stdv=show_stdv))
    callbacks_before_iter, callbacks_after_iter = _split_callbacks(callbacks)

    for i in range(num_boost_round):
        for cb in callbacks_before_iter:
            cb(callback.CallbackEnv(model=None, cvfolds=cvfolds, iteration=i,
                                    begin_iteration=0,
                                    end_iteration=num_boost_round,
                                    evaluation_result_list=None))
        for fold in cvfolds:
            fold.update(fobj)
        res = _agg_cv_result([f.eval(feval) for f in cvfolds])
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks_after_iter:
                cb(callback.CallbackEnv(model=None, cvfolds=cvfolds, iteration=i,
                                        begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=res))
        except callback.EarlyStopException as e:
            for k in results:
                results[k] = results[k][:e.best_iteration + 1]
            break
    return dict(results)
