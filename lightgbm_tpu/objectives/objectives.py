"""Objective implementations. See package docstring for design notes."""

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.trace import scope
from ..utils.log import Log

K_MIN_SCORE = -np.inf


class ObjectiveFunction:
    """Interface (include/LightGBM/objective_function.h:31-32).

    Objectives with a jittable gradient also expose the PURE form
    `_grad_pure(ops, score)` with its device operands `_grad_ops` (a
    pytree of per-row arrays). The fused trainer (models/gbdt.py
    _get_fused_fn) feeds those operands as runtime ARGUMENTS instead of
    letting the jit close over them: closed-over arrays embed their
    VALUES in the lowered HLO, so any label perturbation would change
    the program bytes and defeat the persistent compile cache."""

    name = "none"
    _grad_pure = None   # staticmethod-like (ops, score) -> (g, h)
    _grad_ops = None    # pytree of device operands for _grad_pure

    def init(self, metadata, num_data):
        self.num_data = num_data
        self.label = np.asarray(metadata.label, dtype=np.float32)
        self.weights = (None if metadata.weights is None
                        else np.asarray(metadata.weights, dtype=np.float32))
        # guardrail: a NaN/Inf label or weight poisons every gradient of
        # every iteration — fail at init with the offending row instead
        # of training garbage trees (utils/guardrails.py)
        from ..utils.guardrails import validate_labels
        validate_labels(self.label, self.weights)

    def _install_grad(self, grad_pure, ops):
        """Register a pure gradient: adds the optional row weights to
        `ops`, stores the (_grad_pure, _grad_ops) pair for the fused
        trainer, and keeps the closed-over jitted `_grad` for the
        sequential path."""
        if self.weights is not None:
            ops["weights"] = jnp.asarray(self.weights)
        self._grad_ops = ops
        self._grad_pure = grad_pure
        self._grad = jax.jit(lambda score: grad_pure(ops, score))

    def get_gradients(self, score):
        """score: (K, N) device array -> (grad, hess) each (K, N)."""
        raise NotImplementedError


class RegressionL2loss(ObjectiveFunction):
    """L2 regression (regression_objective.hpp:10-52)."""

    name = "regression"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)

        def _grad_pure(ops, score):
            s = score[0]
            weights = ops.get("weights")
            if weights is not None:
                g = (s - ops["label"]) * weights
                h = jnp.broadcast_to(weights, s.shape)
            else:
                g = s - ops["label"]
                h = jnp.ones_like(s)
            return g[None, :], h[None, :]

        self._install_grad(_grad_pure, {"label": jnp.asarray(self.label)})

    def get_gradients(self, score):
        return self._grad(score)


class BinaryLogloss(ObjectiveFunction):
    """Binary logloss with sigmoid scaling / unbalance / scale_pos_weight
    (binary_objective.hpp:13-109)."""

    name = "binary"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid parameter %f should be greater than zero", self.sigmoid)
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        cnt_positive = int(np.sum(self.label == 1))
        cnt_negative = num_data - cnt_positive
        Log.info("Number of postive: %d, number of negative: %d",
                 cnt_positive, cnt_negative)
        if cnt_positive == 0 or cnt_negative == 0:
            Log.fatal("Training data only contains one class")
        label_weights = [1.0, 1.0]
        if self.is_unbalance:
            if cnt_positive > cnt_negative:
                label_weights[0] = cnt_positive / cnt_negative
            else:
                label_weights[1] = cnt_negative / cnt_positive
        label_weights[1] *= self.scale_pos_weight

        sig = self.sigmoid

        def _grad_pure(ops, score):
            s = score[0]
            sign, lw = ops["sign"], ops["lw"]
            response = -2.0 * sign * sig / (1.0 + jnp.exp(2.0 * sign * sig * s))
            abs_response = jnp.abs(response)
            g = response * lw
            h = abs_response * (2.0 * sig - abs_response) * lw
            weights = ops.get("weights")
            if weights is not None:
                g = g * weights
                h = h * weights
            return g[None, :], h[None, :]

        self._install_grad(_grad_pure, {
            "sign": jnp.asarray(np.where(self.label == 1, 1.0, -1.0),
                                dtype=jnp.float32),
            "lw": jnp.asarray(np.where(self.label == 1, label_weights[1],
                                       label_weights[0]), dtype=jnp.float32),
        })

    def get_gradients(self, score):
        return self._grad(score)


class MulticlassLogloss(ObjectiveFunction):
    """Softmax multiclass (multiclass_objective.hpp:13-94)."""

    name = "multiclass"

    def __init__(self, config):
        self.num_class = int(config.num_class)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label_int = self.label.astype(np.int32)
        if label_int.min() < 0 or label_int.max() >= self.num_class:
            Log.fatal("Label must be in [0, %d), but found %d in label",
                      self.num_class, int(label_int.min() if label_int.min() < 0
                                          else label_int.max()))
        def _grad_pure(ops, score):
            # one pass for all K trees of the iteration, from the score
            # at its start (gbdt.cpp:210-245 computes gradients once,
            # then loops the classes); device sub-scope `softmax`
            # (under `gradients` in the fused step)
            with scope("softmax"):
                p = jax.nn.softmax(score, axis=0)  # (K, N)
                g = p - ops["onehot"]
                h = 2.0 * p * (1.0 - p)
                weights = ops.get("weights")
                if weights is not None:
                    g = g * weights[None, :]
                    h = h * weights[None, :]
            return g, h

        self._install_grad(_grad_pure, {"onehot": jnp.asarray(
            np.eye(self.num_class, dtype=np.float32)[label_int].T)})  # (K, N)

    def get_gradients(self, score):
        return self._grad(score)


class LambdarankNDCG(ObjectiveFunction):
    """LambdaRank with NDCG weighting (rank_objective.hpp:19-227).

    Gradients run ON DEVICE over the length-bucketed query layout
    (rank_device.py), installed as the pure `_grad_pure(ops, score)`
    with the layout's device arrays as `_grad_ops`, like the other
    objectives: the fused multi-iteration trainer takes them as runtime
    arguments. The float64 host path below is kept as the accuracy
    reference (tests pin the two against each other;
    docs/Objectives.md has the tolerance).
    """

    name = "lambdarank"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        self.label_gain = np.asarray(config.label_gain, dtype=np.float64)
        self.optimize_pos_at = int(config.max_position)
        self.min_input = -50.0 / self.sigmoid / 2.0
        self.max_input = -self.min_input

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        from ..metrics.dcg_calculator import DCGCalculator
        self.dcg = DCGCalculator(self.label_gain)
        if metadata.query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        self.num_queries = len(self.query_boundaries) - 1
        from ..telemetry.trace import PROCESS_TRACER
        from .rank_device import (BucketedQueryLayout, lambdarank_grad,
                                  lambdarank_ops)
        with PROCESS_TRACER.span("rank_layout", queries=self.num_queries,
                                 rows=num_data):
            self.layout = BucketedQueryLayout(self.query_boundaries, num_data)
            ops, self.inverse_max_dcgs = lambdarank_ops(
                self.layout, self.label, self.label_gain,
                self.optimize_pos_at)
        sig = self.sigmoid
        self._install_grad(
            lambda ops, score: lambdarank_grad(ops, score, sig), ops)

    def get_gradients(self, score):
        return self._grad(jnp.asarray(score, dtype=jnp.float32).reshape(1, -1))

    def _sigmoid(self, x):
        x = np.clip(x, self.min_input, self.max_input)
        return 2.0 / (1.0 + np.exp(2.0 * x * self.sigmoid))

    def get_gradients_host(self, score):
        score = np.asarray(score, dtype=np.float32).reshape(-1)
        grad = np.zeros_like(score, dtype=np.float64)
        hess = np.zeros_like(score, dtype=np.float64)
        discount = self.dcg.discount
        for q in range(self.num_queries):
            lo, hi = self.query_boundaries[q], self.query_boundaries[q + 1]
            cnt = hi - lo
            if cnt <= 1:
                continue
            s = score[lo:hi].astype(np.float64)
            lab = self.label[lo:hi].astype(np.int64)
            inv_max_dcg = self.inverse_max_dcgs[q]
            order = np.argsort(-s, kind="stable")
            rank_of = np.empty(cnt, dtype=np.int64)
            rank_of[order] = np.arange(cnt)
            best = s[order[0]]
            worst_idx = cnt - 1
            if worst_idx > 0 and s[order[worst_idx]] == K_MIN_SCORE:
                worst_idx -= 1
            worst = s[order[worst_idx]]

            # pair matrix: i = high (larger label), j = low
            lg = self.label_gain[lab]
            dcg_gap = lg[:, None] - lg[None, :]                   # >0 when i higher
            pair_mask = dcg_gap > 0
            disc = discount[np.minimum(rank_of, len(discount) - 1)]
            paired_discount = np.abs(disc[:, None] - disc[None, :])
            delta_ndcg = dcg_gap * paired_discount * inv_max_dcg
            delta_score = s[:, None] - s[None, :]
            if best != worst:
                delta_ndcg = delta_ndcg / (0.01 + np.abs(delta_score))
            p_lambda = self._sigmoid(delta_score)
            p_hess = p_lambda * (2.0 - p_lambda)
            lam = -p_lambda * delta_ndcg * pair_mask
            hes = 2.0 * p_hess * delta_ndcg * pair_mask
            g = lam.sum(axis=1) - lam.sum(axis=0)
            h = hes.sum(axis=1) + hes.sum(axis=0)
            if self.weights is not None:
                g *= self.weights[lo:hi]
                h *= self.weights[lo:hi]
            grad[lo:hi] = g
            hess[lo:hi] = h
        import jax.numpy as jnp
        return (jnp.asarray(grad[None, :], dtype=jnp.float32),
                jnp.asarray(hess[None, :], dtype=jnp.float32))


def create_objective(name, config):
    """Factory (objective_function.cpp:9-20). Returns None for unknown names
    (the C API allows training with custom objectives and objective=none)."""
    name = str(name).lower()
    if name == "regression":
        return RegressionL2loss()
    if name == "binary":
        return BinaryLogloss(config)
    if name == "multiclass":
        return MulticlassLogloss(config)
    if name == "lambdarank":
        return LambdarankNDCG(config)
    if name in ("none", ""):
        return None
    Log.fatal("Unknown objective type name: %s", name)
