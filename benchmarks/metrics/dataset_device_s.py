"""Seconds of `dataset/upload` + `dataset/bin_device` +
`dataset/download` on the program's process tracer (io/dataset.py
_bin_dense_on_device): the part of `dataset_s` that moves data to the
chip, bins it there and brings it back."""

PARTS = ("dataset/upload", "dataset/bin_device", "dataset/download")


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    held = PROCESS_TRACER.snapshot()
    hit = [held[p] for p in PARTS if p in held]
    return sum(hit) if hit else None
