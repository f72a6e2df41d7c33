"""The names a trace is read by: device scopes in the compiled program
(`jax.named_scope`, telemetry/trace.py DEVICE_SCOPES), the kernel's name,
host spans and their annotations, the compile ledger's labels, and the
benchmark's reader of all of them (benchmarks/scopereduce.py).

One file, late in the alphabet: the AOT case compiles for a described
v5e, and only one worker may load the TPU's library. Nothing here waits
on a port, a thread or a sleep.
"""

import contextlib
import json
import os
import re
import struct
import subprocess
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.partitioned import build_tree_partitioned
from lightgbm_tpu.ops.ordered_hist import segment_histograms
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.telemetry.ledger import (_CACHE_HIT_EVENT,
                                           _CACHE_LOAD_EVENT,
                                           _COMPILE_EVENT, LEDGER,
                                           CompileLedger)
from lightgbm_tpu.telemetry.trace import (DEVICE_PATH_WORDS, DEVICE_SCOPES,
                                          DEVICE_SUBSCOPES, KERNEL_NAMES,
                                          PROCESS_TRACER, SpanTracer)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import scopereduce  # noqa: E402
import tracereduce  # noqa: E402

ALL_WORDS = set(DEVICE_SCOPES).union(*DEVICE_SUBSCOPES.values())
# an objective's own words under `gradients` (the pairwise pass of a
# ranking objective, the softmax of a multiclass one): in no binary
# program, and each read by a reader that carries the words itself
# (tests/test_rank_bucketed.py finds the ranking ones in a lambdarank
# program, test_class_axis_words_in_compiled_text the softmax here)
RANK_WORDS = set(DEVICE_SUBSCOPES["gradients"])
BLOCK = 2
ROWS = 5000


@contextlib.contextmanager
def fresh_compiles():
    """jax keys its persistent cache on the program without its debug
    info, so an entry written before a scope existed serves an executable
    without it: what reads names compiles afresh."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def words_in(text):
    """Vocabulary words that stand as a path component of some op_name."""
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        found.update(ALL_WORDS.intersection(path.split("/")))
    return found


def builder(n_pad, w=2, b=16, l=5):
    """build_tree_partitioned at a tiny shape, with a stand-in for the
    collective: on one chip `hist_reduce` is the identity and leaves no
    operation to carry the name."""
    def core(words, g, h, ib, fm, nb, ic):
        return build_tree_partitioned(
            words, g, h, ib, fm, nb, ic, num_leaves=l, max_bin=b,
            params=SplitParams(1.0, 1e-3, 0.0, 0.0, 0.0), max_depth=-1,
            f_real=4 * w, hist_reduce_fn=lambda x: x * 2.0,
            sum_psum_fn=lambda x: x * 2.0)
    f = 4 * w
    shapes = [((w, n_pad), jnp.int32), ((n_pad,), jnp.float32),
              ((n_pad,), jnp.float32), ((n_pad,), jnp.float32),
              ((f,), jnp.bool_), ((f,), jnp.int32), ((f,), jnp.bool_)]
    return core, shapes


@contextlib.contextmanager
def recorded_compiles():
    """The compiled text of every program lowered inside, compiled
    afresh (`fresh_compiles`), as a list that fills as they compile."""
    texts = []
    compile_ = jax.stages.Lowered.compile

    def recording(self, *a, **k):
        out = compile_(self, *a, **k)
        texts.append(out.as_text())
        return out

    with pytest.MonkeyPatch.context() as mp, fresh_compiles():
        mp.setattr(jax.stages.Lowered, "compile", recording)
        yield texts


@pytest.fixture(scope="module")
def trained():
    """A 5,000-row booster (two row chunks: with one, writing a window
    back is writing the whole array and XLA drops it) on the partitioned
    builder, one fused block of BLOCK iterations with a valid set; the
    compiled text of every program it lowered; and what the process
    tracer held once the dataset was dropped. Device binning is forced
    on, as it is on the chip."""
    rng = np.random.RandomState(7)
    x = rng.randn(ROWS, 6).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "partitioned_build": "true", "verbose": -1, "metric": "none"}
    PROCESS_TRACER.reset()
    LEDGER.reset()
    with pytest.MonkeyPatch.context() as mp, recorded_compiles() as texts:
        mp.setenv("LIGHTGBM_TPU_DEVICE_BIN", "1")
        ds = lgb.Dataset(x, label=y, params=dict(params)).construct()
        vs = lgb.Dataset(x[:500], label=y[:500], reference=ds).construct()
        booster = lgb.Booster(params=dict(params), train_set=ds)
        booster.add_valid(vs, "v")
        gbdt = booster.gbdt
        gbdt.train_many(BLOCK, ignore_train_metrics=True)
    assert gbdt.tree_learner._use_partitioned
    del ds, vs
    return {"gbdt": gbdt, "booster": booster, "texts": texts,
            "process_spans": PROCESS_TRACER.recent(None),
            "process_held": PROCESS_TRACER.snapshot(),
            "ledger": LEDGER.snapshot()}


# ----------------------------------------------------- 1. device scopes
def builder_text():
    core, shapes = builder(4 * 4096)
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    with fresh_compiles():
        return jax.jit(core).lower(*args).compile().as_text()


def fused_text(trained):
    fused = [t for t in trained["texts"] if "jit(fused)" in t]
    assert len(fused) == 1
    return fused[0]


def kernel_text():
    """segment_histograms through its kernel branch, interpreted: on CPU
    the builder takes the XLA branch, which has neither kernel nor fold."""
    words = jax.ShapeDtypeStruct((2, 2 * 4096), jnp.int32)
    ghc = jax.ShapeDtypeStruct((3, 2 * 4096), jnp.float32)

    def f(words, ghc, begin, cnt):
        with jax.named_scope("hist"):
            return segment_histograms(words, ghc, begin, cnt, 16, 8,
                                      interpret_backend="tpu",
                                      interpret=True)
    with fresh_compiles():
        return jax.jit(f).lower(words, ghc, jnp.int32(0),
                                jnp.int32(5000)).compile().as_text()


@pytest.mark.parametrize("program,expect", [
    ("builder", ALL_WORDS - RANK_WORDS - {"seg_hist", "fold"}),
    ("fused_step", ALL_WORDS - RANK_WORDS - {"seg_hist", "fold",
                                              "hist_reduce"}),
    ("kernel_branch", {"hist", "window", "seg_hist", "fold"}),
])
def test_device_scopes_in_compiled_text(program, expect, request):
    if program == "builder":
        text = builder_text()
    elif program == "fused_step":
        text = fused_text(request.getfixturevalue("trained"))
    else:
        text = kernel_text()
    assert expect - words_in(text) == set()
    # sub-scopes stand under their own top-level word, nowhere else
    for path in re.findall(r'op_name="([^"]*)"', text):
        parts = path.split("/")
        for top, subs in DEVICE_SUBSCOPES.items():
            for sub in set(subs).intersection(parts):
                assert top in parts[:parts.index(sub)], path


def test_vocabulary_avoids_primitive_names():
    from jax.extend import core as jex_core
    prims = {p.name for p in vars(jax.lax).values()
             if isinstance(p, jex_core.Primitive)}
    assert {"gather", "slice", "scatter", "sort", "cond", "while"} <= prims
    assert ALL_WORDS.union(KERNEL_NAMES, DEVICE_PATH_WORDS).isdisjoint(prims)
    assert scopereduce.VOCABULARY == DEVICE_SCOPES
    # the yardstick's table knows every sub-scope but the objectives' own,
    # which their readers carry (benchmarks/metrics/rank_grad_ms_per_iter.py,
    # softmax_grad_ms_per_iter.py), as class_scan_ms_per_iter.py carries
    # the class axis' path word
    from datagen import load_module
    rank_reader = load_module("metrics", "rank_grad_ms_per_iter")
    softmax_reader = load_module("metrics", "softmax_grad_ms_per_iter")
    assert dict(scopereduce.SUBSCOPES, gradients=rank_reader.SUBSCOPES
                + (softmax_reader.WORD,)) == DEVICE_SUBSCOPES
    assert softmax_reader.TOP == "gradients"
    assert DEVICE_PATH_WORDS == (
        load_module("metrics", "class_scan_ms_per_iter").WORD,)
    assert not set(DEVICE_PATH_WORDS) & ALL_WORDS


def walk_eqns(jaxpr, prefix=()):
    """(primitive name, name-stack words outermost first, equation) of
    every equation, inner jaxprs included: an inner equation's stack is
    relative to the equation that holds its jaxpr."""
    for eqn in jaxpr.eqns:
        stack = prefix + tuple(
            w for w in str(eqn.source_info.name_stack).split("/") if w)
        yield eqn.primitive.name, stack, eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from walk_eqns(inner, stack)


def test_score_update_moves_each_row_once(monkeypatch):
    """The traced fused step of a partitioned learner above the lookup's
    switch length: no N-index gather from a table longer than a piece,
    one N-sized permutation (a key-value sort; no scatter), and everything the un-permute and the lookup trace
    stands under `score_update` -- with the vocabulary as it was."""
    from lightgbm_tpu.models import gbdt as gbdt_mod, partitioned
    from lightgbm_tpu.models.score_updater import LOOKUP_PIECE
    assert DEVICE_SCOPES == ("gradients", "partition", "hist", "hist_reduce",
                             "split_scan", "tree_state", "score_update")
    assert {k: len(v) for k, v in DEVICE_SUBSCOPES.items()} == {
        "gradients": 5, "partition": 6, "hist": 3, "tree_state": 2}
    leaves = 2 * LOOKUP_PIECE + 1
    rng = np.random.RandomState(3)
    x = rng.randn(ROWS, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 15,
              "partitioned_build": "true", "verbose": -1, "metric": "none"}
    booster = lgb.Booster(params=params,
                          train_set=lgb.Dataset(x, label=y, params=params))
    learner = booster.gbdt.tree_learner
    assert learner._use_partitioned and learner.n_pad > ROWS

    def probed(fn):       # marks what the two helpers trace
        def inner(*a, **k):
            with jax.named_scope("probe"):
                return fn(*a, **k)
        return inner

    monkeypatch.setattr(gbdt_mod, "leaf_lookup",
                        probed(gbdt_mod.leaf_lookup))
    monkeypatch.setattr(partitioned, "unpermute",
                        probed(partitioned.unpermute))
    traced = {}
    jit = jax.jit

    class Recorder:
        def __init__(self, fn):
            self.fn = fn

        def lower(self, *args):
            traced["jaxpr"] = jax.make_jaxpr(self.fn)(*args)
            return jit(self.fn).lower(*args)

    monkeypatch.setattr(
        gbdt_mod.jax, "jit",
        lambda fn, *a, **k: (Recorder(fn) if fn.__name__ == "fused"
                             else jit(fn, *a, **k)))
    booster.gbdt._get_fused_fn(1)
    monkeypatch.undo()
    eqns = list(walk_eqns(traced["jaxpr"].jaxpr))
    size = lambda eqn: max(int(np.prod(v.aval.shape)) for v in eqn.outvars)
    new_code = [(name, stack, eqn) for name, stack, eqn in eqns
                if "probe" in stack]
    assert {"gather", "select_n"} <= {name for name, _, _ in new_code}
    for name, stack, eqn in new_code:
        assert "score_update" in stack[:stack.index("probe")], (name, stack)
    lookups = [eqn for name, stack, eqn in eqns
               if name == "gather" and "score_update" in stack
               and size(eqn) >= ROWS]
    assert len(lookups) == -(-leaves // LOOKUP_PIECE)
    for eqn in lookups:
        assert eqn.invars[0].aval.shape[0] <= LOOKUP_PIECE
    moves = [(name, eqn) for name, stack, eqn in eqns
             if name in ("scatter", "scatter-add", "sort")
             and "score_update" in stack and size(eqn) >= learner.n_pad]
    assert [name for name, _ in moves] == ["sort"]
    assert moves[0][1].params["num_keys"] == 1 and len(moves[0][1].invars) == 2


# -------------------------------------- 2. scopereduce on a built XSpace
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, float):
        return varint(no << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


STATS = {1: "tf_op", 2: "bytes_accessed", 3: "peak"}


def plane(name, lines, metadata):
    """lines: [(name, timestamp_ns, [(metadata id, offset_ps, dur_ps)])];
    metadata: {id: (name, tf_op or None, bytes)}."""
    out = field(2, name)
    for lname, t0, events in lines:
        body = field(2, lname) + field(3, t0)
        for mid, off, dur in events:
            body += field(4, field(1, mid) + field(2, off) + field(3, dur))
        out += field(3, body)
    for mid, (mname, tf_op, nbytes) in metadata.items():
        md = field(1, mid) + field(2, mname)
        if tf_op is not None:
            md += field(5, field(1, 1) + field(5, tf_op))
        md += field(5, field(1, 2) + field(3, nbytes))
        md += field(5, field(1, 3) + field(2, 819.0))     # a double stat
        out += field(4, field(1, mid) + field(2, md))
    for sid, sname in STATS.items():
        out += field(5, field(1, sid) + field(2, field(1, sid)
                                              + field(2, sname)))
    return field(1, out)


US = 1_000_000      # ps in a microsecond
P = "jit(fused)/while/body/closed_call/"
# id: (name, tf_op, bytes). The loop (1) and the conditional of the
# partition switch (2) are containers, and carry no tf_op, as in the
# chip's trace; 3-4 are scoped leaves, 5 a bare
# copy (once inside the conditional, once at the loop's level), 6 a copy
# with the loop body's path and no word, 7 the kernel, 8 a zero-length
# buffer allocation that starts in the same ns as the copy after it
DEVICE_MD = {
    1: ("%while.1 = while()", None, 0),
    2: ("%cond.2 = conditional()", None, 0),
    3: ("%fusion.3 = s32[64,7] fusion()",
        P + "partition/cond/branch_1_fun/move/jit(_take)/gather:", 4000),
    4: ("%fusion.4 = s32[64] fusion()",
        P + "partition/cond/branch_1_fun/destinations/cumsum:", 1000),
    5: ("%copy.5 = s32[7,64] copy()", None, 2000),
    6: ("%copy.6 = f32[3,64] copy()", P.rstrip("/"), 3000),
    7: ("%seg_hist.7 = f32[8,128,9] custom-call()",
        P + "hist/cond/branch_0_fun/seg_hist/pallas_call:", 0),
    8: ("%custom-call.8 = custom-call()", None, 0),
}
#            id  offset   duration (us)
DEVICE_EVENTS = [(1, 100, 1000),
                 (6, 110, 40),
                 (2, 200, 500), (3, 210, 300), (5, 520, 100), (4, 630, 50),
                 (7, 800, 150),
                 (8, 960, 0), (5, 960, 20),
                 (6, 1200, 30)]          # after the window: not counted
HOST_MD = {1: ("bench_block", None, 0), 2: ("fused_block", None, 0),
           3: ("fused_block/wait", None, 0), 4: ("valid_update", None, 0),
           5: ("fused_block/guard", None, 0)}
HOST_EVENTS = [(1, 50, 1100), (2, 60, 1000), (3, 100, 900), (4, 1070, 20),
               (5, 1010, 30)]


def write_trace(tmp_path, device_md):
    data = plane("/device:TPU:0",
                 [("XLA Modules", 0, [(1, 0, 5 * US)]),
                  ("XLA Ops", 0, [(m, o * US, d * US)
                                  for m, o, d in DEVICE_EVENTS])],
                 device_md)
    # the host line starts 7 ns in: its events are offset from there
    data += plane("/host:CPU",
                  [("main/1", 7, [(m, o * US - 7000, d * US)
                                  for m, o, d in HOST_EVENTS])], HOST_MD)
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(data)
    return str(d / "host.xplane.pb")


def unscoped_md():
    """The same program from a cache entry written before the scopes."""
    return {k: (n, tf and P + "cond/branch_1_fun/mul:", b)
            for k, (n, tf, b) in DEVICE_MD.items()}


@pytest.mark.parametrize("case", ["per_scope", "inherited_and_unscoped",
                                  "sum_rule", "host_annotations",
                                  "stale_executable", "readers"])
def test_scopereduce_on_built_trace(case, tmp_path, monkeypatch):
    stale = case == "stale_executable"
    path = write_trace(tmp_path, unscoped_md() if stale else DEVICE_MD)
    tab = scopereduce.reduce(scopereduce.read_xspace(path))
    rows = tab["rows"]
    us = lambda key: round(rows[key][0] * 1e6, 6)
    if case == "per_scope":
        assert us(("partition", "move", False)) == 300
        assert us(("partition", "destinations", False)) == 50
        assert us(("hist", "seg_hist", False)) == 150
        assert rows[("partition", "move", False)][1] == 4000
        assert scopereduce.seconds_of(tab, ("partition",)) == \
            pytest.approx(450e-6)
    elif case == "inherited_and_unscoped":
        # the bare copy inside the conditional takes the word all scoped
        # leaves in there agree on, apart; the one with the loop body's
        # path sits in a container of two words and inherits none. A
        # zero-length allocation that starts in the same ns as the copy
        # after it makes that copy a container (tracereduce's leaf rule
        # on whole-ns clocks): its 20 us are in nobody's busy time
        assert us(("partition", "", True)) == 100
        assert us(("unscoped", "", False)) == 40
        assert rows[("unscoped", "", False)][2] == 2
        assert tab["scoped_s"] == pytest.approx(500e-6)
    elif case == "sum_rule":
        devices, host = {}, []
        names = {k: v[0] for k, v in DEVICE_MD.items()}
        devices["/device:TPU:0"] = [
            (names[m], float(o * 1000), float((o + d) * 1000))
            for m, o, d in DEVICE_EVENTS]
        host = [(HOST_MD[m][0], float(o * 1000), float((o + d) * 1000))
                for m, o, d in HOST_EVENTS]
        ref = tracereduce.reduce(devices, host)
        assert tab["busy_s"] == pytest.approx(ref["busy_s"], rel=1e-9)
        assert tab["window_s"] == pytest.approx(ref["window_s"], rel=1e-9)
        words = scopereduce.VOCABULARY + (scopereduce.UNSCOPED,)
        assert scopereduce.seconds_of(tab, words) == \
            pytest.approx(ref["busy_s"], rel=1e-9)
    elif case == "host_annotations":
        assert tab["host"] == pytest.approx(
            {"fused_block": 1000e-6, "fused_block/wait": 900e-6,
             "fused_block/guard": 30e-6, "valid_update": 20e-6})
        assert "bench_block" not in tab["host"]
    elif case == "stale_executable":
        assert tab["scoped_s"] == 0
        assert tab["busy_s"] == pytest.approx(640e-6)
    if case in ("stale_executable", "readers"):
        monkeypatch.setattr(scopereduce, "TRACE_ROOT", str(tmp_path))
        scopereduce.table.cache_clear()
        from datagen import load_module
        ctx = {"trace": {"busy_s": tab["busy_s"]}, "block_iterations": 2}
        read = lambda name: load_module("metrics", name).read(ctx)
        device = ["partition_ms_per_iter", "hist_ms_per_iter",
                  "split_scan_ms_per_iter", "tree_state_ms_per_iter",
                  "boost_ms_per_iter", "unscoped_ms_per_iter"]
        got = {name: read(name) for name in device}
        if stale:
            assert got == dict.fromkeys(device)       # None, never 0
        else:
            assert got["partition_ms_per_iter"] == pytest.approx(0.225)
            assert got["unscoped_ms_per_iter"] == pytest.approx(0.020)
            assert got["split_scan_ms_per_iter"] == 0.0
            assert sum(got.values()) == pytest.approx(
                1e3 * tab["busy_s"] / 2)
        # the host's annotations do not depend on the executable
        assert read("block_host_ms_per_iter") == pytest.approx(0.060)
        # off a TPU the harness hands no trace: nothing is read
        ctx["trace"] = None
        assert [read(n) for n in device + ["block_host_ms_per_iter",
                                          "fused_trace_lower_s",
                                          "dataset_device_s"]] == [None] * 9
        scopereduce.table.cache_clear()


def test_class_axis_words_in_compiled_text_and_trace(tmp_path, monkeypatch):
    """K = 3 under the partitioned builder: the fused step scans the
    classes under the path word `class_scan`, and the one gradient pass
    an iteration stands under `gradients` / `softmax` outside that scan.
    Every operation of a class's tree keeps its own top-level word, so
    what carries `class_scan` and none is the scan's own work; the two
    readers find exactly those in a trace, and nothing in one without
    the words (None, never 0)."""
    rng = np.random.RandomState(11)
    x = rng.randn(ROWS, 6).astype(np.float32)
    y = ((x[:, 0] > 0).astype(int) + (x[:, 1] > 0.3).astype(int)
         ).astype(np.float32)
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "max_bin": 31, "partitioned_build": "true", "verbose": -1}
    with recorded_compiles() as texts:
        booster = lgb.Booster(params=dict(params), train_set=lgb.Dataset(
            x, label=y, params=dict(params)))
        booster.gbdt.train_many(BLOCK, ignore_train_metrics=True)
    gbdt = booster.gbdt
    assert len(gbdt.models) == 3 * BLOCK
    snap = gbdt.metrics.snapshot()
    assert snap["gauges"]["class_axis_form"] == "scan"
    assert snap["gauges"]["trees_per_iteration"] == 3
    assert snap["counters"]["class_trees"] == 3 * BLOCK
    block = [sp for sp in gbdt.tracer.recent(None)
             if sp["path"] == "fused_block"]
    assert block[0]["tags"] == {"iterations": BLOCK, "classes": 3,
                                "first_iter": 0}
    (text,) = [t for t in texts if "jit(fused)" in t]
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)]
    top = lambda parts: next((w for w in parts if w in DEVICE_SCOPES), None)
    scan = [p for p in paths if "class_scan" in p]
    assert scan
    # a class's tree: the builder's words, each after `class_scan`
    assert {top(p) for p in scan} >= {"partition", "hist", "split_scan",
                                      "tree_state", "score_update"}
    # the scan's own work: the word and no top-level one
    assert any(top(p) is None for p in scan)
    # one gradient pass an iteration, outside the class scan
    soft = [p for p in paths if "softmax" in p]
    assert soft and all("gradients" in p[:p.index("softmax")]
                        and "class_scan" not in p for p in soft)

    # ---- the two readers on a built trace
    from datagen import load_module
    md = dict(DEVICE_MD)
    md[5] = (md[5][0], P + "class_scan/while/body/dynamic_slice:", 2000)
    md[6] = (md[6][0], P + "gradients/softmax/exp:", 3000)
    md[4] = (md[4][0], P + "class_scan/while/body/partition/cond/"
             "branch_1_fun/destinations/cumsum:", 1000)
    # inside a class's tree and without a word: the builder's, not the scan's
    md[3] = (md[3][0], P + "class_scan/while/body/closed_call/while/body/"
             "closed_call", 4000)
    ctx = {"trace": {"busy_s": 1.0}, "block_iterations": 2}
    monkeypatch.setattr(scopereduce, "TRACE_ROOT", str(tmp_path / "with"))
    write_trace(tmp_path / "with", md)
    read = lambda name: load_module("metrics", name).read(ctx)
    # id 5 is a leaf for 100 us of the window (its 20 us more span an
    # allocation: a container's, nobody's), id 6 for 40 (30 after it)
    assert read("class_scan_ms_per_iter") == pytest.approx(0.050)
    assert read("softmax_grad_ms_per_iter") == pytest.approx(0.020)
    monkeypatch.setattr(scopereduce, "TRACE_ROOT", str(tmp_path / "without"))
    write_trace(tmp_path / "without", DEVICE_MD)
    assert read("class_scan_ms_per_iter") is None
    assert read("softmax_grad_ms_per_iter") is None
    ctx["trace"] = None           # off a TPU the harness hands no trace
    assert read("class_scan_ms_per_iter") is None
    assert read("softmax_grad_ms_per_iter") is None


# ------------------------------------------------------- 3. host spans
def test_fused_block_child_spans(trained):
    spans = trained["gbdt"].tracer.recent(None)
    by_path = {s["path"]: s for s in spans}
    children = ["masks", "launch", "wait", "tree_fetch", "materialize"]
    for name in children:
        s = by_path["fused_block/" + name]
        assert s["name"] == name and s["duration_s"] > 0
        assert s["tags"] == {"iterations": BLOCK, "classes": 1,
                             "first_iter": 0}
    block = by_path["fused_block"]
    assert sum(by_path["fused_block/" + c]["duration_s"]
               for c in children) <= block["duration_s"]
    assert by_path["valid_update"]["tags"]["iterations"] == BLOCK
    assert len(trained["gbdt"].models) == BLOCK
    assert by_path["fused_block/guard"]["tags"]["iterations"] == BLOCK
    # children count under their path; the journal's deltas stay a
    # partition of wall time (top-level spans only)
    snap = trained["gbdt"].tracer.snapshot()
    assert "fused_block/wait" in snap and "wait" not in snap
    deltas = trained["gbdt"].tracer.delta_snapshot()
    assert set(deltas) <= {"fused_block", "valid_update"}
    counters = trained["gbdt"].metrics.snapshot()["counters"]
    assert counters["fused_blocks"] == 1
    assert counters["tree_build_dispatches"] == BLOCK
    assert "class_trees" not in counters      # one tree an iteration
    # the engine the learner's partition step compiled to, beside it
    gauges = trained["gbdt"].metrics.snapshot()["gauges"]
    assert gauges["trees_per_iteration"] == 1
    assert gauges["class_axis_form"] == "single"
    assert gauges["partition_engine"] == "xla"
    # and the end-of-tree un-permute + leaf-value lookup it traced
    assert gauges["score_update_form"] == "sort_kv+take"


@pytest.mark.parametrize("max_bin,rows,features", [(63, 64, 4),
                                                   (255, 40, 2)])
def test_seg_hist_onehot_gauges(max_bin, rows, features):
    """The histogram kernel's streamed operand, in /trainz: rows a
    feature and features a contraction, from the bins the booster's
    train set has (a 64-row one-hot / 4 up to 64 bins; above, the
    split-bin form's 40 masked statistic rows / 2 at 255 bins), and L,
    the low values of a bin the split-bin form takes (0: the one-hot
    form)."""
    rng = np.random.RandomState(11)
    x = rng.randn(2000, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 2, "max_bin": max_bin,
              "partitioned_build": "true", "verbose": -1, "metric": "none"}
    booster = lgb.Booster(params=params,
                          train_set=lgb.Dataset(x, label=y, params=params))
    assert booster.gbdt.tree_learner.max_bin == max_bin
    booster.gbdt.train_many(1)
    gauges = booster.gbdt.metrics.snapshot()["gauges"]
    assert gauges["seg_hist_onehot_rows"] == rows
    assert gauges["seg_hist_features_per_dot"] == features
    assert gauges["seg_hist_low_bins"] == (0 if max_bin <= 64 else 4)
    # four columns: one word row, the whole accumulator one block, the
    # partition kernel's row one tile of words and a full chunk
    assert gauges["seg_hist_feature_blocks"] == 1
    assert gauges["seg_hist_block_features"] == 4
    assert gauges["partition_rows_words"] == 8
    assert gauges["partition_rows_chunk_lanes"] == 2048
    assert gauges["partition_rows_tile_form"] == "one_perm+roll"
    assert gauges["pos_leaf_form"] == "once_a_tree"


def test_process_tracer_holds_dataset_spans(trained):
    paths = [s["path"] for s in trained["process_spans"]]
    first = paths[:paths.index("dataset") + 1]     # the train set's
    assert first == ["dataset/sample", "dataset/bin_bounds",
                     "dataset/bundle_plan", "dataset/host_prep",
                     "dataset/upload", "dataset/bin_device/dataset_bin",
                     "dataset/bin_device", "dataset/download",
                     "dataset/pack", "dataset/profile", "dataset"]
    tags = trained["process_spans"][0]["tags"]
    assert tags == {"rows": ROWS, "features": 6}
    # a Booster's tracer holds none of it, and the other way round
    assert not any(s["path"].startswith("dataset")
                   for s in trained["gbdt"].tracer.recent(None))
    assert "fused_block" not in paths
    from datagen import load_module
    held = PROCESS_TRACER.snapshot()
    assert load_module("metrics", "dataset_device_s").read(
        {"trace": {"busy_s": 1.0}}) == pytest.approx(
            held["dataset/upload"] + held["dataset/bin_device"]
            + held["dataset/download"])


def test_process_tracer_names_setup(trained):
    """Set-up has its names on the process tracer: the Booster's init
    and its two parts, the bundle plan, the bytes the device binning
    moves, and the fused program's two ledger labels, `:compile` tagged
    with whether the persistent cache served it (here it cannot:
    `fresh_compiles`)."""
    spans = {s["path"]: s for s in trained["process_spans"]}
    for path in ("booster_init", "booster_init/learner",
                 "booster_init/score", "dataset/bundle_plan",
                 f"fused_scan_{BLOCK}it:lower",
                 f"fused_scan_{BLOCK}it:compile"):
        assert spans[path]["duration_s"] > 0, path
    assert spans["booster_init"]["tags"] == {"rows": ROWS, "features": 6,
                                             "classes": 1}
    assert spans[f"fused_scan_{BLOCK}it:compile"]["tags"] == \
        {"cache_hit": False}
    assert spans["dataset/bin_device/dataset_bin"]["tags"] == \
        {"cache_hit": False}
    # 5,000 rows pad to a 65,536-row chunk: float32 up, one byte down
    assert spans["dataset/upload"]["tags"]["bytes"] >= 65536 * 6 * 4
    assert spans["dataset/download"]["tags"]["bytes"] == 65536 * 6
    # the fused program is built by the first block, after the init
    init, lower = spans["booster_init"], spans[f"fused_scan_{BLOCK}it:lower"]
    assert init["start_s"] + init["duration_s"] <= lower["start_s"] + 2e-6
    kids = sum(spans["booster_init/" + c]["duration_s"]
               for c in ("learner", "score"))
    assert kids <= init["duration_s"] + 2e-6


def test_import_is_a_process_span():
    """A fresh process that imports the package holds `import` and its
    child `import/sklearn`, and no other set-up."""
    code = ("import json, lightgbm_tpu\n"
            "from lightgbm_tpu.telemetry.trace import PROCESS_TRACER\n"
            "print(json.dumps(PROCESS_TRACER.snapshot()))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(BENCH))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    held = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(held) == {"import", "import/sklearn"}
    assert held["import"] >= held["import/sklearn"] > 0


def test_span_annotation_carries_the_path():
    seen = []

    class Recorder:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tracer = SpanTracer()
    with mock.patch.object(jax.profiler, "TraceAnnotation", Recorder):
        with tracer.span("outer"):
            with tracer.span("inner", k=1):
                pass
    assert seen == ["outer", "outer/inner"]
    assert not hasattr(tracer, "jax_annotations")


# --------------------------------------------------- 4. compile ledger
def test_ledger_holds_lower_and_compile_seconds(trained):
    held = trained["ledger"]["label_seconds"]
    assert held[f"fused_scan_{BLOCK}it:lower"] > 0
    assert held[f"fused_scan_{BLOCK}it:compile"] > 0
    labels = {e["label"] for e in trained["ledger"]["recent"]}
    assert f"fused_scan_{BLOCK}it:compile" in labels
    # the benchmark's reader takes the same field once the Booster is gone
    from datagen import load_module
    reader = load_module("metrics", "fused_trace_lower_s")
    lower = sum(v for k, v in LEDGER.label_seconds.items()
                if k.startswith("fused_scan_") and k.endswith(":lower"))
    assert reader.read({"trace": {"busy_s": 1.0}}) == pytest.approx(lower)
    # (the snapshot rounds to the microsecond)
    assert lower >= held[f"fused_scan_{BLOCK}it:lower"] - 1e-5


def test_ledger_labels_are_process_spans(trained):
    """Mirroring adds no key to `label_seconds`, and each label's
    seconds are its process span's, under whatever span was open."""
    held = trained["ledger"]["label_seconds"]
    paths = {f"fused_scan_{BLOCK}it:lower": f"fused_scan_{BLOCK}it:lower",
             f"fused_scan_{BLOCK}it:compile":
                 f"fused_scan_{BLOCK}it:compile",
             "dataset_bin": "dataset/bin_device/dataset_bin"}
    assert set(held) == set(paths)
    for label, path in paths.items():
        assert held[label] == pytest.approx(trained["process_held"][path],
                                            abs=1e-6)


def test_ledger_label_span_tags_a_hit():
    led = CompileLedger()
    with PROCESS_TRACER.span("outer"):
        with led.label("fused_scan_3it:compile"):
            led._on_event(_CACHE_HIT_EVENT)
        with led.label("serving_bucket_64"):
            pass
    hit, miss = PROCESS_TRACER.recent(3)[:2]
    assert (hit["path"], hit["tags"]) == \
        ("outer/fused_scan_3it:compile", {"cache_hit": True})
    assert (miss["path"], miss["tags"]) == \
        ("outer/serving_bucket_64", {"cache_hit": False})
    assert set(led.label_seconds) == {"fused_scan_3it:compile",
                                      "serving_bucket_64"}
    assert led.label_seconds["fused_scan_3it:compile"] == \
        pytest.approx(hit["duration_s"], abs=1e-6)
    assert led.current_label() == ""


def test_compile_cache_hits_is_the_ledgers(monkeypatch):
    """One count of persistent-cache hits: the compile ledger's."""
    from lightgbm_tpu import config
    assert config.compile_cache_hits() == LEDGER.cache_hits
    monkeypatch.setattr(LEDGER, "cache_hits", LEDGER.cache_hits + 41)
    assert config.compile_cache_hits() == LEDGER.cache_hits
    assert not [n for n in vars(config) if n.endswith("event_listener")]


@pytest.mark.parametrize("name", ["import_s", "dataset_host_s",
                                  "booster_init_s", "fused_compile_s",
                                  "fused_cache_hit", "guard_ms_per_iter"])
def test_setup_readers(name, tmp_path, monkeypatch):
    """Each reader of PR 37 reads a recorded process tracer (or, for
    the guard, a built trace), and nothing where the harness hands no
    trace."""
    from datagen import load_module
    from lightgbm_tpu.telemetry import trace
    held = SpanTracer()
    for path, secs in (("import", 3.0), ("dataset", 10.0),
                       ("dataset/upload", 1.0), ("dataset/bin_device", 2.0),
                       ("dataset/download", 0.5), ("booster_init", 4.0)):
        held.add(path, secs)
    for hit in (True, False):
        with held.span("fused_scan_3it:compile") as span:
            span.tag(cache_hit=hit)
    monkeypatch.setattr(trace, "PROCESS_TRACER", held)
    monkeypatch.setattr(scopereduce, "TRACE_ROOT", str(tmp_path))
    write_trace(tmp_path, DEVICE_MD)
    scopereduce.table.cache_clear()
    ctx = {"trace": {"busy_s": 1.0}, "block_iterations": 2}
    read = load_module("metrics", name).read
    want = {"import_s": 3.0, "dataset_host_s": 6.5, "booster_init_s": 4.0,
            "fused_compile_s": held.snapshot()["fused_scan_3it:compile"],
            "fused_cache_hit": 0.5, "guard_ms_per_iter": 0.015}[name]
    assert read(ctx) == pytest.approx(want)
    ctx["trace"] = None
    assert read(ctx) is None
    scopereduce.table.cache_clear()


def test_ledger_reads_a_cache_hit_as_a_load():
    """jax 0.9.0 fires backend_compile_duration for a persistent-cache
    hit too, after the hit and its retrieval time: one entry, a load."""
    led = CompileLedger()
    with led.label("fused_scan_3it:compile"):
        led._on_event(_CACHE_HIT_EVENT)
        led._on_duration(_CACHE_LOAD_EVENT, 0.25)
        led._on_duration(_COMPILE_EVENT, 0.3)
        led._on_duration(_COMPILE_EVENT, 2.0)      # a miss: compiled
    snap = led.snapshot()
    assert [(e["cache_hit"], e["seconds"]) for e in snap["recent"]] == \
        [(True, 0.3), (False, 2.0)]
    assert snap["compiles"] == 1 and snap["cache_hits"] == 1
    assert snap["total_s"] == pytest.approx(2.0)
    assert snap["cache_load_s"] == pytest.approx(0.25)
    assert snap["label_seconds"]["fused_scan_3it:compile"] >= 0


# ------------------------------------------------ 5. AOT, described v5e
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_scopes_survive_the_tpu_compiler(one_chip):
    """What the chip's compiler keeps of the names (ISSUE 25, 1a; ISSUE
    26): both kernels under their own names, `partition_rows` under
    `partition` / `move`; every scope the TPU engine writes (all but
    `invert`: there is no inverse permutation to scatter); the
    conditionals of the histogram's switch and of the decision's window
    switch under their words; no gather and no scatter under
    `partition`, and no copy of the rows inside the decision's
    branches."""
    core, shapes = builder(4 * 4096)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    assert ALL_WORDS - RANK_WORDS - words_in(text) == {"invert"}
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    names = set()
    for ln in kernels:
        name = re.match(r"\s*(ROOT )?%([a-z_]+)[.\d]* = ", ln).group(2)
        names.add(name)
        path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
        if name == "partition_rows":
            assert path.index("partition") < path.index("move"), ln[:200]
        else:
            assert name == "seg_hist" and "hist" in path, ln[:200]
            assert "seg_hist" in path
    assert names == {"seg_hist", "partition_rows"}
    assert names <= set(KERNEL_NAMES)
    conditionals = [ln for ln in text.splitlines()
                    if re.search(r" conditional\(", ln)]
    paths = [re.search(r'op_name="([^"]*)"', ln).group(1)
             for ln in conditionals]
    assert any(p.endswith("/hist/cond") for p in paths), paths
    assert any(p.endswith("/partition/cond") for p in paths), paths
    for ln in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', ln)
        if m and "partition" in m.group(1).split("/"):
            assert not re.search(r" (gather|scatter)\(", ln), ln[:200]
            # the rows' two arrays (8 and 4 rows of n_pad) are never
            # copied, transposed or not, for a window to be cut out
            assert not re.search(r"= [sf]32\[[84],16384\]\S* copy\(", ln), \
                ln[:200]
    # the end of the tree: `row_leaf` is `pos_leaf` sorted by `perm`, one
    # two-operand sort and no scatter behind it
    tail = [ln for ln in text.splitlines()
            if re.search(r'op_name="[^"]*/score_update/', ln)]
    assert not any(re.search(r" scatter\(", ln) for ln in tail)
    sorts = [ln for ln in tail if re.search(r" sort\(", ln)]
    assert len(sorts) == 1 and "s32[16384]" in sorts[0], sorts


def top_level_lines(text):
    """The instructions of the computations a fusion does not call: what
    the chip runs as operations of their own (a fusion's body is inside
    its fusion)."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", text))
    out, inside = [], False
    for ln in text.splitlines():
        head = re.match(r"(ENTRY )?(%[\w.\-]+) \(", ln)
        if head:
            inside = head.group(2) not in fused
        elif inside and re.match(r"\s+(ROOT )?%", ln):
            out.append(ln)
    return out


def test_pos_leaf_is_made_once_after_the_split_loop(one_chip):
    """The chip's compiler keeps the position -> leaf map out of the
    split loop: nothing in the loop's body is under `tree_state` /
    `pos_leaf` (no select over all n_pad positions a split), one
    operation outside the loop writes the n_pad int32 values (the
    product's fusion, its one-hot and steps made inside it), and nothing
    under `pos_leaf` gathers, scatters or sorts; the builder scatters
    nothing anywhere."""
    n = 4 * 4096
    core, shapes = builder(n, l=255)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    path = lambda ln: re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
    under = [ln for ln in text.splitlines()
             if re.search(r'op_name="[^"]*/tree_state/pos_leaf/', ln)]
    assert under
    in_loop = [ln for ln in under if "while" in path(ln)]
    assert not in_loop, in_loop[0][:300]
    size = lambda ln: re.match(r"\s*(ROOT )?%\S+ = s32\[([\d,]*)\]", ln)
    whole = [ln for ln in top_level_lines(text)
             if ln in under and size(ln)
             and np.prod([int(d) for d in size(ln).group(2).split(",")
                          if d]) == n]
    assert len(whole) == 1 and " fusion(" in whole[0], whole
    assert not any(re.search(r" (gather|scatter|sort)\(", ln)
                   for ln in under)
    assert not re.search(r" scatter\(", text)


@pytest.mark.parametrize("leaves,classes", [(63, 0), (64, 0), (255, 0),
                                            (255, 3)])
def test_leaf_lookup_compiles_to_selects(one_chip, leaves, classes):
    """The chip's compiler turns every piece of `leaf_lookup` into the
    select chain it keeps for a small table, inside the add's loop
    fusion: no `gather` is left at 63, 64 or 255 leaves, nor for K
    classes looked up a class at a time (`lax.map`, as the fused step's
    vmapped builder does: batched by `vmap` the same lookup compiles to
    gathers again)."""
    from lightgbm_tpu.models.score_updater import leaf_lookup
    n = 4 * 4096
    shrink = jnp.float32(0.1)

    def one(score, table, index):
        return score + leaf_lookup(table * shrink, index[:n])

    def per_class(score, table, index):
        return score + jax.lax.map(
            lambda o: leaf_lookup(o[0] * shrink, o[1][:n]), (table, index))

    k = (classes,) if classes else ()
    args = [jax.ShapeDtypeStruct(k + s, d, sharding=one_chip)
            for s, d in [((n,), jnp.float32), ((leaves,), jnp.float32),
                         ((n + 4096,), jnp.int32)]]
    with fresh_compiles():
        text = jax.jit(per_class if classes else one).lower(
            *args).compile().as_text()
    assert not re.search(r" gather\(", text)
    assert len(re.findall(r" select\(", text)) >= leaves - 1


@pytest.mark.parametrize("f,w,b,result", [
    (28, 8, 63, "f32[7,256,9]"), (136, 40, 63, "f32[34,256,9]"),
    (135, 40, 63, "f32[34,256,9]"), (28, 8, 255, "f32[7,160,128]")])
def test_seg_hist_compiles_at_the_cells_widths(one_chip, f, w, b, result):
    """The chip's compiler takes the histogram kernel at the columns,
    word rows and bins of the benchmark's cells (interpret mode does not
    see tiling or VMEM): one custom call named `seg_hist` whose result is
    the accumulator, a packed word row's four 64-row one-hots stacked at
    63 bins (the unrolled body at 28 columns, the rolled one at 136, a
    partly filled last word row at 135); at 255 the split-bin form's
    word row, four features' 40 masked statistic rows against two
    features' 64-row high one-hots a contraction."""
    from lightgbm_tpu.ops.ordered_hist import _seg_hist_tpu
    from lightgbm_tpu.ops.pallas_hist import HIST_CHUNK
    n_blocks = 8
    n = n_blocks * HIST_CHUNK
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [((w, n), jnp.int32), ((n, 3), jnp.float32),
                         ((), jnp.int32), ((), jnp.int32)]]
    with fresh_compiles():
        text = jax.jit(
            lambda words, ghc, lo, hi: _seg_hist_tpu(
                words, ghc, lo, hi, f, b, n_blocks)
        ).lower(*args).compile().as_text()
    (kernel,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert re.match(rf"\s*(ROOT )?%seg_hist[.\d]* = {re.escape(result)}",
                    kernel), kernel[:200]


@pytest.mark.parametrize("b,blocks", [(63, 16), (255, 63)])
def test_builder_compiles_at_2000_columns(one_chip, b, blocks):
    """The chip's compiler takes the tree builder at Epsilon's 2,000
    columns (500 words a row, 504 in the partition kernel's array): the
    histogram kernel with its feature axis (16 blocks of 128 features at
    63 bins, 63 of 32 at 255, the split-bin accumulator there) and the
    partition kernel at 512-lane chunks, each under its own name and
    scope."""
    from lightgbm_tpu.ops.ordered_hist import feature_blocks
    from lightgbm_tpu.ops.partition import chunk_lanes, packed_word_rows
    assert feature_blocks(2000, b)[0] == blocks
    assert chunk_lanes(packed_word_rows(500)) == 512
    core, shapes = builder(2 * 4096, w=500, b=b, l=3)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    names = set()
    for ln in kernels:
        name = re.match(r"\s*(ROOT )?%([a-z_]+)[.\d]* = ", ln).group(2)
        names.add(name)
        path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
        if name == "partition_rows":
            assert "s32[504,8192]" in ln, ln[:200]
            assert path.index("partition") < path.index("move"), ln[:200]
        else:
            acc = "f32[500,256,9]" if b == 63 else "f32[500,160,128]"
            assert acc in ln, ln[:200]
            assert path.index("hist") < path.index("seg_hist"), ln[:200]
    assert names == {"seg_hist", "partition_rows"}


@pytest.mark.parametrize("wp,lanes", [(8, 2048), (40, 2048), (504, 512)])
def test_partition_rows_compiles_at_the_cells_widths(one_chip, wp, lanes):
    """The chip's compiler takes the partition kernel alone at the word
    rows of the benchmark's cells (8: 28 or 13 columns; 40: 136; 504:
    2,000) and their chunks: the tile's one permutation and the two
    lane rolls by a shift read at run time lower through Mosaic at every
    width, and the compiled text holds one custom call `partition_rows`
    on the rows' array."""
    from lightgbm_tpu.ops.partition import chunk_lanes, partition_rows
    assert chunk_lanes(wp) == lanes
    n = 4 * 2048
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [((wp, n), jnp.int32), ((4, n), jnp.float32),
                         ((n,), jnp.bool_), ((), jnp.int32),
                         ((), jnp.int32), ((), jnp.int32)]]
    with fresh_compiles():
        text = jax.jit(partition_rows).lower(*args).compile().as_text()
    (kernel,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert re.match(r"\s*(ROOT )?%partition_rows[.\d]* = ", kernel), \
        kernel[:200]
    assert f"s32[{wp},{n}]" in kernel, kernel[:200]


@pytest.mark.parametrize("columns,w,b,window_rows", [
    (28, 7, 63, [4096, 8192, 16384]), (28, 7, 255, [4096, 8192, 16384]),
    (136, 34, 63, [4096, 8192, 16384]),
    (2000, 500, 63, [512, 1024, 2048, 4096, 8192, 16384])])
def test_seg_hist_windows_follow_the_row(one_chip, columns, w, b,
                                         window_rows):
    """The windows the histogram kernel is compiled for, from the
    builder's compiled text at four chunks of rows (ISSUE 36): at the
    narrow cells' shapes (28 x 63, 28 x 255, 136 x 63) exactly the whole
    chunks 1, 2, 4 the parent compiled, at both call sites; at 2,000 x
    63 three rungs under a chunk before them. The kernel's second
    operand is the window of packed words, the third the statistics'
    nine terms over the same rows (lane-major at 255 bins, the split-bin
    form's); every call sits under `hist`."""
    from lightgbm_tpu.ops.ordered_hist import min_rows
    from lightgbm_tpu.ops.partition import packed_word_rows
    assert min_rows(4 * w, b) == window_rows[0]
    wp = packed_word_rows(w)
    core, shapes = builder(4 * 4096, w=w, b=b, l=3)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    seen = []
    for body in hlo_computations(text).values():
        for name, ln in body.items():
            if "tpu_custom_call" not in ln or not name.startswith("seg_hist"):
                continue
            path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
            assert path.index("hist") < path.index("seg_hist"), ln[:200]
            _, words, stats = re.search(
                r"custom-call\(([^)]*)\)", ln).group(1).split(", ")
            shape = [re.match(r"\s+(?:ROOT )?%\S+ = (\w+\[[\d,]*\])",
                              body[op.lstrip("%")]).group(1)
                     for op in (words, stats)]
            rows = int(re.match(r"s32\[(\d+),(\d+)\]", shape[0]).group(2))
            terms = f"bf16[9,{rows}]" if b > 64 else f"bf16[{rows},9]"
            assert shape == [f"s32[{wp},{rows}]", terms], shape
            seen.append(rows)
    # the root's call site and the loop's: every rung once at each
    assert sorted(seen) == sorted(2 * window_rows), seen


def hlo_computations(text):
    """{computation: {instruction: its line}} of a compiled module's
    text, both in the text's order."""
    comps, current = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", ln)
        if head:
            current = comps.setdefault(head.group(1), {})
        inst = re.match(r"\s+(?:ROOT )?%(\S+) = ", ln)
        if inst and current is not None:
            current[inst.group(1)] = ln
    return comps


@pytest.mark.parametrize("which,leaves,columns,bins", [
    ("partitioned", 255, 136, 63), ("partitioned", 63, 28, 255),
    ("masked", 63, 28, 300)])
def test_split_writes_two_rows_of_the_cache_in_place(one_chip, which, leaves,
                                                     columns, bins):
    """A split updates the histogram cache where it lies (ISSUE 34): the
    chip's compiler leaves no `copy` (nor `copy-start`) of the cache's
    shape anywhere in the builder, and inside the loop exactly two
    instructions give a result of that shape: fusions of a
    `dynamic-update-slice` on their own first parameter, the first on
    the split branch's own parameter (the carried buffer), the second
    on the first's result. At the parent the parent's row was read
    again after the first write, and the same text held two whole-array
    copies a split (`copy.215`, `copy.300` at Epsilon's shapes). How to
    look: docs/Observability.md, "A carried array copied whole"."""
    n_pad = 8 * 4096
    core, shapes = builder(n_pad, w=columns // 4, b=bins, l=leaves)
    if which == "masked":
        from lightgbm_tpu.models.tree_learner import build_tree_device
        shapes[0] = ((columns, n_pad), jnp.int32)

        def core(rows, g, h, ib, fm, nb, ic):
            return build_tree_device(
                rows, g, h, ib, fm, nb, ic, num_leaves=leaves, max_bin=bins,
                params=SplitParams(1.0, 1e-3, 0.0, 0.0, 0.0), max_depth=-1,
                row_chunk=4096)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    cache = rf"f32\[{leaves},{columns},{bins},3\]"
    assert re.search(cache, text)
    copies = [ln.strip()[:200] for ln in text.splitlines()
              if re.search(rf" = \(?{cache}[^=]*? copy(-start)?\(", ln)]
    assert not copies, copies
    comps = hlo_computations(text)
    writes = [(comp, name, ln) for comp, body in comps.items()
              if not comp.startswith("fused_computation")
              for name, ln in body.items()
              if re.search(rf" = {cache}\S* (?!get-tuple-element)\S+\(", ln)
              and "/while/body/" in ln]
    assert len(writes) == 2, [ln.strip()[:200] for _, _, ln in writes]
    for _, _, ln in writes:
        if which == "partitioned":
            assert re.search(r'/tree_state/hist_cache/[^/"]*"', ln), ln[:300]
        called = comps[re.search(r"fusion\(.*calls=%([^\s,]+)",
                                 ln).group(1)]
        (root,) = [r for r in called.values() if " ROOT " in r]
        target = re.search(r" dynamic-update-slice\(%([^\s,)]+)",
                           root).group(1)
        assert re.search(r" parameter\(0\)", called[target]), root[:300]
    (branch, first, first_ln), (_, _, second_ln) = writes
    operand = re.search(r" fusion\(%([^\s,)]+)", first_ln).group(1)
    source = comps[branch][operand]
    carried = re.search(r" get-tuple-element\(%([^\s,)]+)\)", source).group(1)
    assert re.search(r" parameter\(0\)", comps[branch][carried]), source
    assert re.search(rf" fusion\(%{re.escape(first)},", second_ln)


# ------------------------------------- the data-parallel exchange (PR 41)
def test_hist_reduce_survives_the_data_parallel_fused_scan():
    """The fused block of `tree_learner=data` over 4 of the 8 virtual
    devices, compiled afresh: its histogram all-reduces (the root's and
    the split loop's) carry `hist_reduce` on their op_name, and the block
    holds no other collective (rows stay on their device)."""
    rng = np.random.RandomState(11)
    x = rng.randn(ROWS, 6).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "partitioned_build": "true", "verbose": -1, "metric": "none",
              "tree_learner": "data", "num_machines": 4}
    with recorded_compiles() as texts:
        ds = lgb.Dataset(x, label=y, params=dict(params)).construct()
        lgb.train(dict(params), ds, num_boost_round=BLOCK)
    (text,) = [t for t in texts if "jit(fused)" in t]
    reduces = [ln for ln in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", ln)]
    assert len(reduces) >= 2
    for ln in reduces:
        path = re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
        assert "hist_reduce" in path, ln[:300]
    assert not [ln for ln in text.splitlines() if re.search(
        r" (all-gather|all-to-all|collective-permute)(-start)?\(", ln)]


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    from jax.sharding import Mesh
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices), ("data",))


def test_data_parallel_builder_compiles_for_four_chips(four_chips):
    """The chip's compiler takes the data-parallel partitioned builder
    over a described v5e 2x2 at the Criteo cell's width (67 columns in 17
    word rows, 255 bins): both kernels on each chip under their names,
    the histogram's all-reduce under `hist_reduce`, and no collective
    that would move rows between chips."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import AXIS, compressed_psum, shard_map
    w, b, shard = 17, 255, 2 * 4096
    psum = functools.partial(compressed_psum, axis_name=AXIS)

    def part(words, g, h, ib, fm, nb, ic):
        out = build_tree_partitioned(
            words, g, h, ib, fm, nb, ic, num_leaves=3, max_bin=b,
            params=SplitParams(100.0, 10.0, 0.0, 0.0, 0.0), max_depth=-1,
            f_real=67, hist_reduce_fn=psum)
        return out["leaf_value"], out["row_leaf"]
    core = shard_map(part, mesh=four_chips,
                     in_specs=(P(None, AXIS), P(AXIS), P(AXIS), P(AXIS),
                               P(), P(), P()),
                     out_specs=(P(), P(AXIS)))
    n = 4 * shard
    sh = lambda *spec: NamedSharding(four_chips, P(*spec))  # noqa: E731
    args = [jax.ShapeDtypeStruct(s, d, sharding=h) for s, d, h in [
        ((w, n), jnp.int32, sh(None, AXIS)), ((n,), jnp.float32, sh(AXIS)),
        ((n,), jnp.float32, sh(AXIS)), ((n,), jnp.float32, sh(AXIS)),
        ((4 * w,), jnp.bool_, sh()), ((4 * w,), jnp.int32, sh()),
        ((4 * w,), jnp.bool_, sh())]]
    with fresh_compiles(), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(core).lower(*args).compile().as_text()
    names = {re.match(r"\s*(ROOT )?%([a-z_]+)", ln).group(2)
             for ln in text.splitlines() if "tpu_custom_call" in ln}
    assert names == {"seg_hist", "partition_rows"}
    reduces = [ln for ln in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", ln)]
    assert reduces
    for ln in reduces:
        assert "hist_reduce" in re.search(r'op_name="([^"]*)"',
                                          ln).group(1).split("/"), ln[:300]
    assert not [ln for ln in text.splitlines() if re.search(
        r" (all-gather|all-to-all|collective-permute)(-start)?\(", ln)]
