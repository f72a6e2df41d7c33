"""Seconds of the span `import` on the program's process tracer
(lightgbm_tpu/__init__.py): the package's own import, its scikit-learn
wrappers (`import/sklearn`) among it. jax and the TPU's start, which
the runner has before it imports the package, are not in it. A program
without the span (the parent of PR 37) reports nothing."""


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    return PROCESS_TRACER.snapshot().get("import")
