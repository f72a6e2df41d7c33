"""Wall seconds the program's compile ledger holds under
`fused_scan_<n>it:lower` (models/gbdt.py _get_fused_fn): tracing and
lowering the fused program, which no compilation cache serves."""


def read(ctx):
    if not ctx.get("trace"):
        return None
    from lightgbm_tpu.telemetry.ledger import LEDGER
    held = getattr(LEDGER, "label_seconds", {})
    hit = [v for k, v in held.items()
           if k.startswith("fused_scan_") and k.endswith(":lower")]
    return sum(hit) if hit else None
