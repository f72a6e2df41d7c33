"""Seconds of the span `dataset/bin_categorical` on the program's
process tracer (io/dataset.py): the categorical columns binned, by
equality against the kept ids in a program of their own on the device,
or by BinMapper.value_to_bin on the host. A program without the span
(the parent of the categorical cell's PR) reports nothing."""


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    return PROCESS_TRACER.snapshot().get("dataset/bin_categorical")
