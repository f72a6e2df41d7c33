"""Public Dataset / Booster API.

Reference: python-package/lightgbm/basic.py. The reference wraps the C
API through ctypes (`_InnerDataset`/`Booster` over `LGBM_*` handles,
basic.py:29-52); here the same public surface delegates directly to the
JAX core (io.dataset.CoreDataset, models.gbdt.GBDT) — no FFI boundary,
the "handle" is the Python object itself.

Mirrored semantics:
- lazy `Dataset` that constructs on first use, aligns bin mappers via
  `reference=`, supports `subset()` and `free_raw_data` (basic.py:413-1183);
- `_InnerPredictor` chaining for continued training: a predictor attached
  to a Dataset seeds init scores, and the new Booster merges the
  predictor's trees (basic.py:182-390, 1227-1231);
- `Booster.update()` with optional custom objective `fobj(preds, dataset)`
  (basic.py:1304-1372), eval/eval_train/eval_valid with `feval`,
  save/dump, split-count feature importance, attr dict (basic.py:1184-1677).
"""

import numpy as np

from .config import Config, key_alias_transform
from .io.dataset import DatasetLoader
from .io.parser import parse_text_file
from .metrics import create_metric
from .models.gbdt import create_boosting
from .objectives import create_objective
from .utils.log import LightGBMError, Log


def is_str(s):
    return isinstance(s, str)


def _coerce_2d(data):
    """numpy 2-D / pandas / scipy-sparse / list-of-rows -> float32 ndarray."""
    if hasattr(data, "toarray"):          # scipy sparse
        data = data.toarray()
    if hasattr(data, "values") and not isinstance(data, np.ndarray):  # pandas
        data = data.values
    arr = np.asarray(data, dtype=np.float32)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return np.ascontiguousarray(arr)


def _coerce_label(label):
    if label is None:
        return None
    if hasattr(label, "values") and not isinstance(label, np.ndarray):
        label = label.values
    return np.asarray(label, dtype=np.float32).reshape(-1)


class _InnerPredictor:
    """Raw-score predictor used for prediction and init-score chaining
    (basic.py:182-390)."""

    def __init__(self, model_file=None, booster=None):
        if model_file is not None:
            self.gbdt = create_boosting("gbdt", model_file)
            with open(model_file) as f:
                self.gbdt.load_model_from_string(f.read())
        elif booster is not None:
            self.gbdt = booster
        else:
            raise TypeError("Need Model file or Booster to create a predictor")
        self.num_class = self.gbdt.num_class

    @property
    def num_total_iteration(self):
        return len(self.gbdt.models) // max(self.gbdt.num_class, 1)

    def predict(self, data, num_iteration=-1, raw_score=False,
                pred_leaf=False, data_has_header=False, is_reshape=True):
        if is_str(data):
            _, feats, _, _, _ = parse_text_file(
                data, has_header=data_has_header, label_column="")
            data = feats
        data = _coerce_2d(data)
        if pred_leaf:
            return self.gbdt.predict_leaf_index(data, num_iteration)
        if raw_score:
            out = self.gbdt.predict_raw(data, num_iteration)
        else:
            out = self.gbdt.predict(data, num_iteration)
        if is_reshape and self.num_class == 1:
            return out.reshape(-1)
        return out if is_reshape else out.reshape(-1, order="F")


class Dataset:
    """Lazy dataset (basic.py:893-1183): stores raw inputs, constructs the
    binned CoreDataset on first use (so `reference=` alignment and the
    predictor for continued training can be attached before binning)."""

    def __init__(self, data, label=None, max_bin=255, reference=None,
                 weight=None, group=None, silent=False, feature_name=None,
                 categorical_feature=None, params=None, free_raw_data=True):
        self.data = data
        self.label = _coerce_label(label)
        self.max_bin = max_bin
        self.reference = reference
        self.weight = weight
        self.group = group
        self.silent = silent
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self.init_score = None
        self._predictor = None
        self._core = None              # CoreDataset once constructed
        self._used_indices = None      # set by subset()
        self._parent = None

    # ------------------------------------------------------------- laziness
    def __is_constructed(self):
        return self._core is not None

    def construct(self) -> "Dataset":
        if self._core is not None:
            return self
        if self._parent is not None:   # subset path (basic.py:1012-1035)
            parent_core = self._parent.construct()._core
            self._core = parent_core.subset(self._used_indices)
            self._apply_fields()
            return self
        params = key_alias_transform(dict(self.params))
        params.setdefault("max_bin", self.max_bin)
        if self.silent:
            params.setdefault("verbose", 0)
        cfg = Config.from_params(params)
        loader = DatasetLoader(cfg)
        ref_core = None
        if self.reference is not None:
            if not isinstance(self.reference, Dataset):
                raise TypeError("Reference dataset should be None or dataset")
            ref_core = self.reference.construct()._core
            self._set_predictor(self.reference._predictor)
        categorical = self._resolve_categorical()
        if is_str(self.data):
            if ref_core is not None:
                self._core = loader.load_from_file_align_with_other_dataset(
                    self.data, ref_core)
            else:
                self._core = loader.load_from_file(self.data)
        else:
            # column sources (CscColumns from the C API's sparse inputs)
            # pass through untouched: one column densifies at a time.
            # NOT a bare hasattr(.col) test — scipy COO matrices have a
            # `.col` ndarray and must keep densifying via _coerce_2d.
            from .io.dataset import is_column_source
            mat = (self.data if is_column_source(self.data)
                   else _coerce_2d(self.data))
            self._core = loader.construct_from_matrix(
                mat, label=self.label, reference=ref_core,
                categorical_features=categorical, group=self.group)
        if self.feature_name is not None:
            self._core.feature_names = list(self.feature_name)
        self._apply_fields()
        self._apply_predictor_init_score()
        if self.free_raw_data and not is_str(self.data):
            self.data = None
        return self

    def _resolve_categorical(self):
        cats = []
        if self.categorical_feature:
            for c in self.categorical_feature:
                if is_str(c):
                    if not self.feature_name:
                        raise LightGBMError(
                            "categorical_feature by name needs feature_name")
                    cats.append(self.feature_name.index(c))
                else:
                    cats.append(int(c))
        return cats

    def _apply_fields(self):
        meta = self._core.metadata
        if self.weight is not None:
            meta.set_weights(np.asarray(self.weight, dtype=np.float32).reshape(-1))
        if self.group is not None:
            meta.set_query(np.asarray(self.group, dtype=np.int64).reshape(-1))
        if self.init_score is not None:
            meta.set_init_score(
                np.asarray(self.init_score, dtype=np.float64).reshape(-1))

    def _apply_predictor_init_score(self):
        """Seed init scores from the chained predictor (basic.py:523-536)."""
        if self._predictor is None:
            return
        if self._core.metadata.init_score is not None:
            return
        if self.data is None and self._core.raw_data is None:
            raise LightGBMError(
                "Cannot set predictor after freed raw data, "
                "Set free_raw_data=False when construct Dataset to avoid this.")
        data = self.data if self.data is not None else self._core.raw_data
        raw = self._predictor.predict(data, raw_score=True, is_reshape=True,
                                      data_has_header=False)
        raw = np.asarray(raw, dtype=np.float64)
        if raw.ndim == 2:              # (N, K) row-major -> class-major flat
            init = raw.T.reshape(-1)
        else:
            init = raw.reshape(-1)
        self._core.metadata.set_init_score(init)

    # ----------------------------------------------------------- public API
    def create_valid(self, data, label=None, weight=None, group=None,
                     silent=False, params=None):
        """New Dataset aligned with self (basic.py:947-971)."""
        return Dataset(data, label=label, max_bin=self.max_bin, reference=self,
                       weight=weight, group=group, silent=silent, params=params)

    def subset(self, used_indices, params=None):
        """Row subset sharing this dataset's bin mappers (basic.py:1012-1035)."""
        ret = Dataset(None, max_bin=self.max_bin, params=params or self.params)
        ret._parent = self
        ret._used_indices = np.asarray(used_indices, dtype=np.int64)
        ret._predictor = self._predictor
        return ret

    def set_reference(self, reference):
        self.reference = reference
        self._set_predictor(reference._predictor)

    def _set_predictor(self, predictor):
        if predictor is self._predictor:
            return
        self._predictor = predictor
        if self._core is not None and predictor is not None:
            self._apply_predictor_init_score()

    def set_feature_name(self, feature_name):
        if feature_name is not None:
            self.feature_name = list(feature_name)
            if self._core is not None:
                self._core.feature_names = list(feature_name)

    def set_categorical_feature(self, categorical_feature):
        if categorical_feature is None:
            return
        if self.__is_constructed():
            Log.warning("categorical_feature set after Dataset was "
                        "constructed; it will not take effect")
        self.categorical_feature = categorical_feature

    def set_label(self, label):
        self.label = _coerce_label(label)
        if self._core is not None and self.label is not None:
            self._core.metadata.set_label(self.label)

    def set_weight(self, weight):
        self.weight = weight
        if self._core is not None and weight is not None:
            self._core.metadata.set_weights(
                np.asarray(weight, dtype=np.float32).reshape(-1))

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._core is not None and init_score is not None:
            self._core.metadata.set_init_score(
                np.asarray(init_score, dtype=np.float64).reshape(-1))

    def set_group(self, group):
        self.group = group
        if self._core is not None and group is not None:
            self._core.metadata.set_query(
                np.asarray(group, dtype=np.int64).reshape(-1))

    def get_label(self):
        if self._core is not None:
            return self._core.metadata.label
        return self.label

    def get_weight(self):
        if self._core is not None:
            return self._core.metadata.weights
        return self.weight

    def get_init_score(self):
        if self._core is not None:
            return self._core.metadata.init_score
        return self.init_score

    def get_group(self):
        if self._core is not None and self._core.metadata.query_boundaries is not None:
            return np.diff(self._core.metadata.query_boundaries)
        return self.group

    def num_data(self):
        return self.construct()._core.num_data

    def num_feature(self):
        return self.construct()._core.num_features

    def save_binary(self, filename):
        self.construct()._core.save_binary(filename)


class Booster:
    """Training/prediction handle (basic.py:1184-1677)."""

    def __init__(self, params=None, train_set=None, model_file=None,
                 silent=False):
        self.best_iteration = -1
        self._attr = {}
        self.__train_data_name = "training"
        self.__train_dataset = None
        self.__valid_datasets = []
        self.__name_valid_sets = []
        self.gbdt = None
        self.config = None
        self.objective = None
        self.__init_predictor = None
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            params = dict(params) if params else {}
            if silent:
                params.setdefault("verbose", 0)
            self.config = Config.from_params(params)
            train_set.construct()
            core = train_set._core
            self.objective = create_objective(self.config.objective, self.config)
            if self.objective is None:
                Log.warning("Using self-defined objective function")
            else:
                self.objective.init(core.metadata, core.num_data)
            train_metrics = self._create_metrics(core)
            self.gbdt = create_boosting(self.config.boosting_type)
            self.gbdt.init(self.config, core, self.objective, train_metrics)
            self.__train_dataset = train_set
            self.__init_predictor = train_set._predictor
            if self.__init_predictor is not None:
                self.gbdt.merge_from(self.__init_predictor.gbdt)
        elif model_file is not None:
            self.gbdt = _InnerPredictor(model_file=model_file).gbdt
        else:
            raise TypeError("At least need training dataset or model file "
                            "to create booster instance")

    # ------------------------------------------------------------- plumbing
    def _create_metrics(self, core):
        metrics = []
        for name in (self.config.metric or ()):
            m = create_metric(name, self.config)
            if m is None:
                continue
            m.init(core.metadata, core.num_data)
            metrics.append(m)
        return metrics

    def set_train_data_name(self, name):
        self.__train_data_name = name

    def add_valid(self, data, name):
        """basic.py:1252-1280."""
        if data._predictor is not self.__init_predictor:
            raise LightGBMError("Add validation data failed, you should use "
                                "same predictor for these data")
        data.construct()
        metrics = self._create_metrics(data._core)
        self.gbdt.add_valid_dataset(data._core, metrics)
        self.__valid_datasets.append(data)
        self.__name_valid_sets.append(name)

    def reset_parameter(self, params):
        """basic.py:1282-1302. Fast path: only the shrinkage rate changes
        (learning-rate schedules call this every iteration)."""
        params = key_alias_transform(dict(params))
        if set(params.keys()) <= {"learning_rate"}:
            if "learning_rate" in params:
                lr = float(params["learning_rate"])
                self.config.learning_rate = lr
                self.gbdt.shrinkage_rate = lr
            return
        merged = {**self._config_as_params(), **params}
        self.config = Config.from_params(merged)
        core = self.gbdt.train_data
        self._reset_objective(core)
        self.gbdt.reset_training_data(
            self.config, core, self.objective,
            self.gbdt.training_metrics)

    def _reset_objective(self, core):
        """Recreate + re-init the objective against `core`, as the
        reference's Booster::ResetTrainingData does (c_api.cpp:63-75) —
        the objective caches label/weight views of the old dataset."""
        if self.objective is None:
            return  # custom-objective mode stays custom
        self.objective = create_objective(self.config.objective, self.config)
        if self.objective is None:
            Log.warning("Using self-defined objective function")
        else:
            self.objective.init(core.metadata, core.num_data)

    def _config_as_params(self):
        from dataclasses import fields as dc_fields
        return {f.name: getattr(self.config, f.name)
                for f in dc_fields(type(self.config))
                if f.name not in ("is_parallel", "is_parallel_find_bin", "seed")}

    # ------------------------------------------------------------- training
    def update(self, train_set=None, fobj=None):
        """One boosting iteration (basic.py:1304-1341). Returns True when
        no further splits are possible (is_finished)."""
        if train_set is not None and train_set is not self.__train_dataset:
            if train_set._predictor is not self.__init_predictor:
                raise LightGBMError("Replace training data failed, you should "
                                    "use same predictor for these data")
            train_set.construct()
            self.__train_dataset = train_set
            self._reset_objective(train_set._core)
            self.gbdt.reset_training_data(
                self.config, train_set._core, self.objective,
                self._create_metrics(train_set._core))
        if fobj is None:
            return self.gbdt.train_one_iter(is_eval=False)
        grad, hess = fobj(self.__inner_predict(0), self.__train_dataset)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess):
        grad = np.asarray(grad, dtype=np.float32).reshape(-1)
        hess = np.asarray(hess, dtype=np.float32).reshape(-1)
        n = self.gbdt.num_data * self.gbdt.num_class
        if len(grad) != n or len(hess) != n:
            raise ValueError("Length of grad and hess should be equal with "
                             "num_data * num_class")
        return self.gbdt.train_one_iter(grad, hess, is_eval=False)

    def rollback_one_iter(self):
        self.gbdt.rollback_one_iter()

    def current_iteration(self):
        return len(self.gbdt.models) // max(self.gbdt.num_class, 1)

    # ----------------------------------------------------------- evaluation
    def __inner_predict(self, data_idx):
        """Transformed predictions of a bound dataset, class-major flat
        (basic.py:1646-1677)."""
        return self.gbdt.get_predict_at(data_idx)

    def __inner_eval(self, data_name, data_idx, feval=None):
        ret = []
        names = self.gbdt.get_eval_names(data_idx)
        values = self.gbdt.get_eval_at(data_idx)
        metrics = (self.gbdt.training_metrics if data_idx == 0
                   else self.gbdt.valid_metrics[data_idx - 1])
        factors = []
        for m in metrics:
            factors.extend([m.factor_to_bigger_better] * len(m.names))
        for name, value, fac in zip(names, values, factors):
            ret.append((data_name, name, value, fac > 0))
        if feval is not None:
            dataset = (self.__train_dataset if data_idx == 0
                       else self.__valid_datasets[data_idx - 1])
            feval_ret = feval(self.__inner_predict(data_idx), dataset)
            if isinstance(feval_ret, list):
                for name, value, bigger in feval_ret:
                    ret.append((data_name, name, value, bigger))
            else:
                name, value, bigger = feval_ret
                ret.append((data_name, name, value, bigger))
        return ret

    def eval(self, data, name, feval=None):
        if data is self.__train_dataset:
            return self.eval_train(feval)
        for i, vd in enumerate(self.__valid_datasets):
            if data is vd:
                return self.__inner_eval(name, i + 1, feval)
        raise LightGBMError("Cannot evaluate Dataset that was not used "
                            "during training")

    def eval_train(self, feval=None):
        return self.__inner_eval(self.__train_data_name, 0, feval)

    def eval_valid(self, feval=None):
        out = []
        for i, name in enumerate(self.__name_valid_sets):
            out.extend(self.__inner_eval(name, i + 1, feval))
        return out

    # ----------------------------------------------------------- prediction
    def predict(self, data, num_iteration=-1, raw_score=False,
                pred_leaf=False, data_has_header=False, is_reshape=True):
        predictor = _InnerPredictor(booster=self.gbdt)
        return predictor.predict(data, num_iteration, raw_score, pred_leaf,
                                 data_has_header, is_reshape)

    def _to_predictor(self):
        return _InnerPredictor(booster=self.gbdt)

    # -------------------------------------------------------- serialization
    def save_model(self, filename, num_iteration=-1):
        self.gbdt.save_model_to_file(num_iteration, filename)

    def dump_model(self):
        return self.gbdt.dump_model()

    def feature_importance(self, importance_type="split"):
        """Per-feature importance ndarray from the split ledger
        (telemetry/quality.py), reference semantics: `split` = int64
        count of splits using the feature (basic.py:1587-1601),
        `gain` = float64 sum of split gain over those splits (the
        C API's LGBM_BoosterFeatureImportance gain variant), `coeff` =
        float64 gain-weighted |coefficient| sums over linear leaves
        (linear_tree=true models; all-zero otherwise — see
        docs/Linear-Trees.md)."""
        from .telemetry.quality import IMPORTANCE_TYPES
        if importance_type not in IMPORTANCE_TYPES:
            raise LightGBMError(
                f"Unknown importance type {importance_type!r}: expected "
                f"one of {IMPORTANCE_TYPES}")
        return self.gbdt.feature_importance_values(importance_type)

    # ---------------------------------------------------------------- attrs
    def attr(self, key):
        return self._attr.get(key)

    def set_attr(self, **kwargs):
        for key, value in kwargs.items():
            if value is None:
                self._attr.pop(key, None)
            else:
                self._attr[key] = str(value)
