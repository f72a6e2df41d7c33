"""Share of the traced block's splits taken on a categorical column, in
percent, counted in the block's trees. Which columns are categorical is
the tag `columns` of the program's process span `dataset/bin_categorical`
(io/dataset.py: the input's indices, as a tree's `split_feature` has
them); a program without that span reports nothing."""

import numpy as np


def categorical_columns():
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    spans = [s for s in PROCESS_TRACER.recent(None)
             if s["path"] == "dataset/bin_categorical"
             and "columns" in s.get("tags", {})]
    return spans[-1]["tags"]["columns"] if spans else None


def read(ctx):
    if not ctx.get("trace") or not ctx.get("trees"):
        return None
    cols = categorical_columns()
    if cols is None:
        return None
    feats = np.concatenate([np.asarray(t["split_feature"]) for t in ctx["trees"]])
    return 100.0 * float(np.isin(feats, cols).mean()) if len(feats) else None
