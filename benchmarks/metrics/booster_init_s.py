"""Seconds of the span `booster_init` on the program's process tracer
(models/gbdt.py GBDT.init): the tree learner's set-up (`learner`: row
padding, word packing, bins to the device) and the train score
(`score`); the objective's own set-up (`rank_layout`) comes before it.
A program without the span (the parent of PR 37) reports nothing."""


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    return PROCESS_TRACER.snapshot().get("booster_init")
