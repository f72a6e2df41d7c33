"""Seconds of the span `dataset` on the program's process tracer less
its device children `dataset/upload`, `dataset/bin_device` and
`dataset/download` (io/dataset.py): the host's share of constructing
the dataset (`sample`, `bin_bounds`, `bundle_plan`, `host_prep`, `pack`,
`profile`, and whatever of it has no span)."""

DEVICE_PARTS = ("dataset/upload", "dataset/bin_device", "dataset/download")


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    held = PROCESS_TRACER.snapshot()
    if "dataset" not in held:
        return None
    return held["dataset"] - sum(held.get(p, 0.0) for p in DEVICE_PARTS)
