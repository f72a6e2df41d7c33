"""Stand-in reference for the harness's tests, with the signature a
configuration's own reference has. It is no learner: it holds the
program to what the seam must hand over. The (K, n) score has to be the
sum, class by class, of the leaf values its own descent of the
class-major trees reaches on the raw matrix."""

import numpy as np


def leaf_values(tree, x):
    """Leaf value of every row: descend by the real thresholds."""
    out = np.full(len(x), float(tree["leaf_value"][0]) if len(
        tree["split_feature"]) == 0 else 0.0)
    node, active = np.zeros(len(x), np.int64), np.arange(len(x))
    while len(active) and len(tree["split_feature"]):
        nd = node[active]
        left = x[active, tree["split_feature"][nd]] <= tree["threshold"][nd]
        nxt = np.where(left, tree["left_child"][nd], tree["right_child"][nd])
        node[active] = nxt
        done = nxt < 0
        out[active[done]] = tree["leaf_value"][~nxt[done]]
        active = active[~done]
    return out


def compare(x, y, fields, params, trees, score_after):
    k = int(params.get("num_class", 1))
    n = len(y)
    score = np.asarray(score_after, np.float64)
    out = {"fields_missing": float(len({"group", "weight"} - set(fields))),
           "group_rows_gap": float(abs(int(np.sum(fields.get("group", 0))) - n)),
           "score_shape_gap": float(score.shape != (k, n)),
           "partial_iterations": float(len(trees) % k)}
    own = np.zeros((k, n))
    for i, tree in enumerate(trees):
        own[i % k] += leaf_values(tree, x)
    out["score_max_gap"] = (float(np.max(np.abs(score - own)))
                            if score.shape == own.shape else 1.0)
    return out
