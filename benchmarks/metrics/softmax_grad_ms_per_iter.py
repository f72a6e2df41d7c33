"""Device time of the multiclass gradient in the traced block, per
iteration: the operations under the sub-scope `softmax` of `gradients`
(objectives/objectives.py MulticlassLogloss: softmax over the (K, N)
score, g = p - onehot, h = 2 p (1 - p); once an iteration, for all K
trees). The pads and the stat block every objective has under
`gradients` are not in it (`boost_ms_per_iter` has them).

`scopereduce.SUBSCOPES` has no entry for `gradients`, so this reader
carries the word and reads the `tf_op` paths itself
(`class_scan_ms_per_iter.leaf_seconds`). None where no operation in
the window carries the word: another objective, or a program compiled
before the word existed.
"""

from metrics.class_scan_ms_per_iter import ms_of

TOP, WORD = "gradients", "softmax"


def read(ctx):
    return ms_of(ctx, lambda parts: TOP in parts
                 and WORD in parts[parts.index(TOP) + 1:])
