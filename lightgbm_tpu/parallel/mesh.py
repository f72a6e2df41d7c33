"""Mesh topology + communication layer shared by the parallel learners.

Reference: src/network/ (Bruck allgather, recursive-halving
reduce-scatter) and the sync points of the three parallel tree learners
(src/treelearner/*parallel_tree_learner.cpp). The reference hand-rolls
its collectives over TCP/MPI; here the transport is XLA collectives
over a `jax.sharding.Mesh`, and THIS module is the one place that knows

- how the mesh is built (`make_mesh`) and how feature ownership is
  derived from it (`MeshTopology`): shard r of W owns the contiguous
  feature block [r*f_loc, (r+1)*f_loc). An elastic shrink
  (lightgbm_tpu/supervisor.py) relaunches with a smaller world, the
  learner re-derives the topology from the new mesh, and ownership
  re-shards automatically — the mesh, not just the machine list.
- the histogram-exchange algorithms and their numerics
  (`pair_allreduce`, `pair_reduce_scatter`, `compressed_*`): the
  deterministic fixed-order Kahan reduction that carries the
  serial == data-parallel bit-parity contract, and the lossy
  `comm_precision` compressions applied at the collective boundary
  only.
- what every collective COSTS (`CommPlan` + the `*_recv_bytes` wire
  models), feeding the `collective_bytes{kind}` counters in the
  metrics registry (telemetry/registry.py -> /trainz, Prometheus
  /metricz, per-iteration journal records).

Exchange algorithms, per tree node, W shards, H = F*B*3*4 bytes of
f32 histogram:

- **allgather-pair** (`hist_exchange=allgather`, the pre-mesh-layer
  path): both Kahan words of the FULL histogram to every rank —
  2*(W-1)*H received per rank. Every rank then reduces and searches
  all features.
- **reduce-scatter** (`hist_exchange=auto|reduce_scatter`, the
  reference DataParallelTreeLearner design): one all_to_all moves each
  rank's slice of every peer's histogram — 2*(W-1)/W*H per rank at
  `comm_precision=pair` (W× less than allgather-pair), (W-1)/W*H at
  `f32`, half that at `bf16`. Each rank Kahan-reduces and searches
  only its OWNED feature block; the global best split is an
  allgather+argmax of one tiny SplitInfo per rank.
- **voting** (PV-Tree): histograms stay local; only the <=2k voted
  features' histograms are psum'd — 2*(W-1)/W * (2k/F)*H per rank.

The all_to_all formulation (rather than `lax.psum_scatter`) is what
preserves bit-parity: every source shard's contribution arrives
SEPARATELY and is folded in a fixed order identical on every shard
and identical to the allgather-pair path, so `comm_precision=pair`
reduce-scatter histograms equal the allgather-pair histograms bit for
bit on the owned block.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..utils.log import Log

AXIS = "data"


def shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map without the replication-consistency checker: the
    builders return replicated tree arrays computed from psum'd
    histograms, which the checker cannot prove replicated."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(config) -> Mesh:
    """1-D device mesh.

    Multi-host (jax.distributed initialized, parallel/distributed.py):
    span ALL global devices — `num_machines` already chose the process
    count. Single-process: num_machines>1 limits the device count so
    tests can model the reference's `num_machines` param; default: all
    local devices."""
    devs = jax.devices()
    n = len(devs)
    if (jax.process_count() == 1 and config is not None
            and getattr(config, "num_machines", 1) > 1):
        n = min(config.num_machines, len(devs))
    return Mesh(np.asarray(devs[:n]), (AXIS,))


# ------------------------------------------------------------ precision

COMM_PRECISIONS = ("pair", "f32", "bf16")


def resolve_comm_precision(config):
    """Validate the `comm_precision` knob: "pair" (default, the
    bit-parity Kahan-word exchange), "f32" (collapsed single word, half
    the bytes, deterministic but ~1e-7-relative), "bf16" (quarter the
    bytes, lossy — AUC-tolerance territory)."""
    p = str(getattr(config, "comm_precision", "pair")).lower()
    if p not in COMM_PRECISIONS:
        Log.fatal("comm_precision must be one of %s, got [%s]",
                  "|".join(COMM_PRECISIONS), p)
    return p


def resolve_hist_exchange(config):
    """Validate `hist_exchange`: auto | reduce_scatter | allgather."""
    e = str(getattr(config, "hist_exchange", "auto")).lower()
    if e not in ("auto", "reduce_scatter", "allgather"):
        Log.fatal("hist_exchange must be auto|reduce_scatter|allgather, "
                  "got [%s]", e)
    return e


# ------------------------------------------------- deterministic kahan

def kahan_fold(components):
    """Fold stacked components (K, ...) in FIXED index order with
    compensated summation — the reduction whose order-independence from
    shard count/topology carries the serial == data-parallel contract
    (the collective analog of the reference's f64 accumulators,
    bin.h:18-26). Every exchange path shares this exact fold so their
    results are mutually bit-comparable."""
    def kstep(carry, x):
        s, c = carry
        y = x - c
        t = s + y
        return (t, (t - s) - y), None

    zero = jnp.zeros_like(components[0])
    (s, c), _ = jax.lax.scan(kstep, (zero, zero), components)
    return s - c


# ------------------------------------------------- exchange algorithms
#
# All operate on per-shard histograms of shape (..., F, B, 3) — the
# feature axis sits at ndim-3 (leading axes are frontier leaf batches).

def pair_allreduce(pair, axis_name=AXIS):
    """Allgather-pair exchange: all_gather BOTH compensated words, fold
    the 2W components in fixed order on every shard. Every rank ends
    with the identical FULL global histogram (the pre-reduce-scatter
    data-parallel path; kept as `hist_exchange=allgather` for
    comparison and for bundled datasets)."""
    hi, lo = pair
    ghi = jax.lax.all_gather(hi, axis_name)          # (W, ..., F, B, 3)
    glo = jax.lax.all_gather(lo, axis_name)
    return kahan_fold(jnp.concatenate([ghi, glo], axis=0))


def compressed_allreduce(pair, axis_name=AXIS, precision="f32"):
    """Allgather exchange at reduced precision: collapse the pair to
    one word per shard (half the bytes), optionally bf16 on the wire
    (quarter), fold the W received words in fixed order."""
    hi, lo = pair
    word = hi + lo
    if precision == "bf16":
        word = word.astype(jnp.bfloat16)
    g = jax.lax.all_gather(word, axis_name).astype(jnp.float32)
    return kahan_fold(g)


def _scatter_feature_groups(x, n_shards, fg_count, axis_name=AXIS):
    """Split `x` (..., F, B, 3) into `fg_count` feature-shard groups and
    all_to_all each group independently. Returns a list of
    (W, ..., fg, B, 3) received stacks — group g holds every source
    shard's contribution for THIS shard's g-th owned sub-slice, stacked
    in source-shard order (the fixed fold order).

    Ownership stays contiguous: shard r owns [r*f_loc, (r+1)*f_loc),
    and group g covers its [g*fg, (g+1)*fg) sub-slice. Issuing the
    groups as independent collectives is the compute/comms overlap
    hook: split evaluation of group g depends only on group g's
    exchange, so XLA's latency-hiding scheduler can keep the collective
    for group g+1 in flight while group g is being searched."""
    lead = x.shape[:-3]
    f, b, s = x.shape[-3:]
    w = n_shards
    f_loc = f // w
    fg = f_loc // fg_count
    ax = len(lead)
    xw = x.reshape(*lead, w, f_loc, b, s)
    outs = []
    for g in range(fg_count):
        blk = xw[..., :, g * fg:(g + 1) * fg, :, :]
        blk = blk.reshape(*lead, w * fg, b, s)
        recv = jax.lax.all_to_all(blk, axis_name, split_axis=ax,
                                  concat_axis=ax, tiled=True)
        recv = recv.reshape(*lead, w, fg, b, s)
        outs.append(jnp.moveaxis(recv, ax, 0))      # (W, ..., fg, B, 3)
    return outs


def pair_reduce_scatter(pair, n_shards, groups=1, axis_name=AXIS):
    """Reduce-scatter exchange at `comm_precision=pair`: one all_to_all
    per word per group, then the fixed-order Kahan fold of the 2W
    received components — bit-identical per owned feature to what
    `pair_allreduce` computes for that feature, at 1/W of the wire
    bytes. Returns this shard's OWNED (..., f_loc, B, 3) block."""
    hi, lo = pair
    his = _scatter_feature_groups(hi, n_shards, groups, axis_name)
    los = _scatter_feature_groups(lo, n_shards, groups, axis_name)
    parts = [kahan_fold(jnp.concatenate([h, l], axis=0))
             for h, l in zip(his, los)]
    return jnp.concatenate(parts, axis=-3)


def compressed_reduce_scatter(pair, n_shards, groups=1, axis_name=AXIS,
                              precision="f32"):
    """Reduce-scatter at reduced precision: collapse the pair locally
    (half the pair bytes), optionally bf16 on the wire (quarter), fold
    the W received words per group in fixed source order (still
    deterministic, no longer serial-bit-parity)."""
    hi, lo = pair
    word = hi + lo
    if precision == "bf16":
        word = word.astype(jnp.bfloat16)
    parts = [kahan_fold(recv.astype(jnp.float32))
             for recv in _scatter_feature_groups(word, n_shards, groups,
                                                 axis_name)]
    return jnp.concatenate(parts, axis=-3)


def compressed_psum(x, axis_name=AXIS, precision="pair"):
    """psum with the comm_precision compression applied at the wire:
    bf16 halves the on-wire word; "pair"/"f32" keep the plain f32 psum
    (psum-based call sites — the partitioned cores, the voting
    learner's selective reduction — are already single-word)."""
    if precision == "bf16":
        return jax.lax.psum(x.astype(jnp.bfloat16),
                            axis_name).astype(jnp.float32)
    return jax.lax.psum(x, axis_name)


# ------------------------------------------------------ wire-byte model
#
# Received bytes per rank for each collective, `nbytes` = one shard's
# input payload. Standard models: allgather receives every peer's
# payload; all_to_all receives 1/W of every peer's; ring allreduce
# (psum) moves the payload twice minus the local share.

def allgather_recv_bytes(nbytes, w):
    return int((w - 1) * nbytes)


def alltoall_recv_bytes(nbytes, w):
    return int((w - 1) * nbytes // max(w, 1))


def psum_recv_bytes(nbytes, w):
    return int(2 * (w - 1) * nbytes // max(w, 1))


COLLECTIVE_KINDS = ("hist_reduce", "split_gather", "leaf_sync")


class CommPlan:
    """Per-tree collective-byte ledger of one learner configuration.

    Collective shapes are static, so the learner declares, per kind,
    the bytes exchanged once per TREE (root build) and per SPLIT; after
    each tree the driver calls `account(metrics, n_splits)` with the
    realized split count (models/gbdt.py train_one_iter) and the
    registry's `collective_bytes_{kind}` counters advance by exactly
    the wire model. `per_tree()` is the closed form dist_probe and the
    docs' comms math quote."""

    def __init__(self):
        self.root = {k: 0 for k in COLLECTIVE_KINDS}
        self.per_split = {k: 0 for k in COLLECTIVE_KINDS}

    def add(self, kind, root=0, per_split=0):
        if kind not in self.root:
            raise ValueError(f"unknown collective kind {kind!r}")
        self.root[kind] += int(root)
        self.per_split[kind] += int(per_split)
        return self

    def per_tree(self, n_splits):
        return {k: self.root[k] + self.per_split[k] * int(n_splits)
                for k in COLLECTIVE_KINDS}

    def account(self, metrics, n_splits):
        """Advance the counters by one tree of `n_splits` splits: the
        bytes of each kind, and the histogram reductions themselves,
        `hist_reduce_calls` (one for the root, one a split) and
        `hist_reduce_bytes` (their received bytes)."""
        total = 0
        for kind, nbytes in self.per_tree(n_splits).items():
            if nbytes:
                metrics.inc(f"collective_bytes_{kind}", nbytes)
                total += nbytes
        if total:
            metrics.inc("collective_bytes", total)
        reduced = self.per_tree(n_splits)["hist_reduce"]
        if reduced:
            metrics.inc("hist_reduce_calls", 1 + int(n_splits))
            metrics.inc("hist_reduce_bytes", reduced)
        return total


class MeshTopology:
    """The learner-facing view of one mesh: shard/process counts,
    feature ownership math, and the resolved comm knobs. Rebuilt at
    every learner init — which is what makes elastic shrink re-shard
    feature ownership and collective topology rather than just the
    machine list: the supervisor relaunches with the survivor world,
    init derives a fresh mesh, and this object (journaled as a `mesh`
    event) is the proof."""

    def __init__(self, mesh, config=None, axis=AXIS):
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.devices.size)
        self.n_proc = int(jax.process_count())
        self.comm_precision = resolve_comm_precision(config) \
            if config is not None else "pair"
        self.hist_exchange = resolve_hist_exchange(config) \
            if config is not None else "auto"
        groups = int(getattr(config, "comm_groups", 1) or 1) \
            if config is not None else 1
        self.comm_groups = max(groups, 1)

    def feature_shard(self, f_pad):
        """Owned-block length of a W-divisible padded feature count."""
        assert f_pad % self.n_shards == 0, (f_pad, self.n_shards)
        return f_pad // self.n_shards

    def owned_block(self, shard, f_pad):
        """(lo, hi) feature block shard `shard` owns — the shared
        jax-free ownership rule (parallel/machines.py), so the
        supervisor's view and the traced builder's `start = shard *
        f_loc` can never disagree."""
        from .machines import partition_features
        return partition_features(f_pad, self.n_shards, shard)

    def owned_block_range(self, shard, num_blocks):
        """(lo, hi) BLOCK range rank `shard` owns over a shared
        out-of-core block store — the shared jax-free ownership rule
        (parallel/machines.py partition_blocks). Like feature ownership
        above, this is re-derived from the CURRENT world at every
        learner init, which is what makes an elastic shrink/grow
        re-shard blocks (journaled as a `block_reshard` event) instead
        of forcing a re-bin."""
        from .machines import partition_blocks
        return partition_blocks(num_blocks, self.n_proc, shard)

    def exchange_groups(self, f_loc):
        """Largest group count <= comm_groups dividing the owned block
        (group boundaries must tile f_loc exactly)."""
        g = min(self.comm_groups, max(f_loc, 1))
        while f_loc % g:
            g -= 1
        return g

    def describe(self, f_pad=None):
        d = {"shards": self.n_shards, "processes": self.n_proc,
             "precision": self.comm_precision,
             "exchange": self.hist_exchange}
        if f_pad is not None:
            f_loc = f_pad // self.n_shards if f_pad % self.n_shards == 0 \
                else None
            d["f_pad"] = int(f_pad)
            if f_loc is not None:
                d["f_loc"] = int(f_loc)
        return d
