"""Host time of the block's score guard, per iteration: the program's
`fused_block/guard` annotation (models/gbdt.py _run_fused_block: the
train score pulled to the host and scanned for non-finite values), read
on the profiler's clock. It lies inside `fused_block` and outside
`fused_block/wait`, so `block_host_ms_per_iter` holds it too."""

from scopereduce import host_spans


def read(ctx):
    spans = host_spans(ctx)
    if not spans or "fused_block/guard" not in spans:
        return None
    return 1e3 * spans["fused_block/guard"] / ctx["block_iterations"]
