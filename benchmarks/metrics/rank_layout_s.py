"""Seconds of the span `rank_layout` on the program's process tracer
(objectives/objectives.py LambdarankNDCG.init): building the
length-bucketed query layout and every query's ideal DCG at set-up. A
program without the span (the parent of PR 29) reports nothing."""


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    return PROCESS_TRACER.snapshot().get("rank_layout")
