"""Seconds of the process spans `fused_scan_<n>it:compile`
(models/gbdt.py _get_fused_fn, the compile ledger's label mirrored on
the process tracer): the fused program's backend compile, or its load
from the persistent cache; `fused_cache_hit` says which. A program
without the spans (the parent of PR 37) reports nothing."""

import re

PATH = re.compile(r"(^|/)fused_scan_\d+it:compile$")


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    hit = [v for k, v in PROCESS_TRACER.snapshot().items() if PATH.search(k)]
    return sum(hit) if hit else None
