"""Host time of the traced block outside the wait for the device, per
iteration: the program's `fused_block` annotation less its
`fused_block/wait` child, plus `valid_update` (models/gbdt.py
_run_fused_block, train_many), read on the profiler's clock."""

from scopereduce import host_spans


def read(ctx):
    spans = host_spans(ctx)
    if not spans or "fused_block" not in spans:
        return None
    host = (spans["fused_block"] - spans.get("fused_block/wait", 0.0)
            + spans.get("valid_update", 0.0))
    return 1e3 * host / ctx["block_iterations"]
