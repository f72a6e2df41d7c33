"""The tag `cache_hit` of the process spans `fused_scan_<n>it:compile`
(telemetry/ledger.py: whether a persistent-cache hit fired inside the
label): 1 where the fused program was loaded, 0 where it was compiled,
the share where several were. A program without the tag (the parent
of PR 37) reports nothing."""

import re

PATH = re.compile(r"(^|/)fused_scan_\d+it:compile$")   # fused_compile_s's


def read(ctx):
    if not ctx.get("trace"):
        return None
    try:
        from lightgbm_tpu.telemetry.trace import PROCESS_TRACER
    except ImportError:     # a program from before the process tracer
        return None
    tags = [s["tags"]["cache_hit"] for s in PROCESS_TRACER.recent(None)
            if PATH.search(s["path"]) and "cache_hit" in s.get("tags", {})]
    return sum(map(float, tags)) / len(tags) if tags else None
