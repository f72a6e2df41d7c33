"""Multi-host distributed runtime: membership + global mesh + placement.

Reference: src/network/linkers_socket.cpp:20-207 (machine-list parsing,
rank discovery, TCP handshake), src/network/network.cpp (Init), and the
per-rank data distribution of src/io/dataset_loader.cpp:505-550.

TPU-first design: membership and transport are `jax.distributed` —
every process calls `initialize(coordinator, num_processes, rank)`, the
mesh spans all global devices, and XLA routes the builder's `lax.psum`
/ `all_gather` over ICI/DCN. The reference's hand-rolled Bruck /
recursive-halving algorithms and socket linkers have no analog: topology
and algorithm selection belong to the compiler. What remains of the
reference's Network class is exactly this file: find my rank, connect,
and expose helpers to build global arrays from per-rank data.

Rank discovery mirrors linkers_socket.cpp:58-86: match a local
hostname/IP against the machine list; the LIGHTGBM_TPU_RANK env var
overrides (needed e.g. for multiple ranks on one host).
"""

import os
import time

import jax
import numpy as np

from ..utils import faults
from ..utils.log import Log
# machine-list parsing + rank discovery live in the jax-free
# parallel/machines.py (the supervisor process reads machine lists
# without importing jax); re-exported here for existing import paths
from .machines import (_local_addresses, _split_host_port,  # noqa: F401
                       find_local_rank, format_machine_list,
                       parse_machine_list)

_initialized = False


def _call_initialize(coordinator, num_processes, rank, timeout_s):
    """One jax.distributed.initialize attempt. Split out so the fault
    harness (`fail_distributed_init`) and tests can intercept it."""
    if faults.consume("fail_distributed_init"):
        raise RuntimeError("injected distributed-init failure")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=rank,
                               initialization_timeout=timeout_s)


def _initialize_with_retry(coordinator, num_processes, rank, retries=3,
                           backoff_s=1.0, timeout_s=120,
                           collectives="default"):
    """jax.distributed.initialize with a per-attempt timeout and
    exponential-backoff retries (TPU fleets routinely restart the
    coordinator pod first; a transient connect failure must not kill
    every worker). Every structured log line names the chosen
    collectives implementation (gloo vs the backend default) — the
    first thing to check when a multi-host bring-up fails is whether
    the CPU client even HAS cross-process collectives, and the journal
    must answer that without shell access to the dead host. Returns
    True on success, False when the backend was already initialized
    externally; fatal when retries are exhausted."""
    delay = max(0.0, float(backoff_s))
    last_error = None
    for attempt in range(int(retries) + 1):
        try:
            _call_initialize(coordinator, num_processes, rank, timeout_s)
            if attempt:
                Log.info("jax.distributed.initialize succeeded on "
                         "attempt %d (collectives=%s)", attempt + 1,
                         collectives)
            return True
        except RuntimeError as e:
            msg = str(e)
            # jax raises "distributed.initialize should only be called
            # once."
            if "only be called once" in msg.lower():
                # backend already up (e.g. an external launcher
                # initialized distributed itself) — keep going with it
                Log.warning("jax.distributed.initialize skipped "
                            "(collectives=%s): %s", collectives, msg)
                return False
            last_error = msg
        if attempt < retries:
            Log.warning("jax.distributed.initialize failed (attempt "
                        "%d/%d, coordinator %s, rank %d of %d, "
                        "collectives=%s): %s; retrying in %.1fs",
                        attempt + 1, retries + 1, coordinator, rank,
                        num_processes, collectives, last_error, delay)
            if delay > 0:
                time.sleep(delay)
            delay = min(delay * 2 if delay > 0 else 1.0, 30.0)
    Log.fatal("jax.distributed.initialize failed after %d attempts "
              "(coordinator %s, rank %d of %d, collectives=%s): %s",
              retries + 1, coordinator, rank, num_processes, collectives,
              last_error)


def init_from_config(config):
    """Bring up jax.distributed from the reference's network config
    (machine_list_file / num_machines, include/LightGBM/config.h:219-226).
    No-op when already initialized or single-machine."""
    global _initialized
    if _initialized:
        return False
    if config is None or config.num_machines <= 1 or not config.machine_list_file:
        return False
    if not os.path.exists(config.machine_list_file):
        if os.environ.get("LIGHTGBM_TPU_RANK") is not None:
            # explicit multi-process launch: training solo here while
            # peers block in jax.distributed.initialize would deadlock
            # the job — die fast like the reference's socket linker
            Log.fatal("machine_list_file %s not found (rank %s)",
                      config.machine_list_file,
                      os.environ["LIGHTGBM_TPU_RANK"])
        # single-process run of a distributed conf (e.g. the reference's
        # examples/parallel_learning out of the box): model num_machines
        # with local mesh devices (parallel/learners.py make_mesh)
        Log.warning("machine_list_file %s not found; running single-"
                    "process with %d mesh devices",
                    config.machine_list_file, config.num_machines)
        return False
    machines = parse_machine_list(config.machine_list_file)
    if len(machines) < config.num_machines:
        Log.fatal("Machine list file only contains %d machines, but "
                  "num_machines is %d", len(machines), config.num_machines)
    machines = machines[:config.num_machines]
    env_rank = os.environ.get("LIGHTGBM_TPU_RANK")
    rank = int(env_rank) if env_rank is not None else find_local_rank(machines)
    if not 0 <= rank < config.num_machines:
        # a wrong LIGHTGBM_TPU_RANK (or a machine list edited out from
        # under a running job) must die loudly HERE: passing it through
        # would hang every healthy peer in the coordinator handshake
        Log.fatal("rank %d is out of range for num_machines=%d "
                  "(machine list %s has %d usable entries); check "
                  "LIGHTGBM_TPU_RANK against the machine list",
                  rank, config.num_machines, config.machine_list_file,
                  len(machines))
    faults.set_rank(rank)  # rank-targeted fault injection + heartbeats
    Log.set_rank(rank)     # rank-attributable interleaved child logs
    coordinator = f"{machines[0][0]}:{machines[0][1]}"
    # CPU multi-process collectives need an explicit implementation
    # (the default CPU client refuses cross-process computations with
    # "Multiprocess computations aren't implemented"); gloo ships with
    # this jax and is what the 2-process CPU test harness runs on. A
    # TPU backend ignores the knob; absent knob (API drift) means CPU
    # multi-host was unsupported anyway, so best-effort is correct.
    collectives = "default"
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        collectives = "gloo"
    except Exception:
        pass
    # NOTE: must run before anything initializes the XLA backend —
    # do not touch jax.devices()/process_count() above this line
    if not _initialize_with_retry(coordinator, config.num_machines, rank,
                                  retries=getattr(config, "init_retries", 3),
                                  backoff_s=getattr(config, "init_backoff_s",
                                                    1.0),
                                  timeout_s=getattr(config, "time_out", 120),
                                  collectives=collectives):
        return False
    _initialized = True
    Log.info("Distributed: rank %d of %d (coordinator %s), %d global "
             "devices, collectives=%s", rank, config.num_machines,
             coordinator, len(jax.devices()), collectives)
    return True


def process_rank():
    return jax.process_index()


def num_processes():
    return jax.process_count()


def is_multi_host():
    return jax.process_count() > 1


def place_global_rows(sharding, local_array):
    """Assemble a row-sharded global array from each process's local
    block (the analog of per-rank row storage, dataset_loader.cpp:505-550)."""
    return jax.make_array_from_process_local_data(sharding, local_array)


def place_replicated(sharding, full_array):
    """Global array whose value every process holds fully (bin matrices
    for feature-parallel, feature masks, per-feature tables)."""
    full_array = np.asarray(full_array)
    return jax.make_array_from_callback(
        full_array.shape, sharding, lambda idx: full_array[idx])


def partition_rows(n, rank, num_machines, query_boundaries=None):
    """Contiguous per-rank row range, aligned to query boundaries so no
    query is split (dataset_loader.cpp distributes rows; contiguous
    blocks give identical global histograms, hence identical trees).
    Returns (lo, hi)."""
    if query_boundaries is not None:
        qb = np.asarray(query_boundaries)
        nq = len(qb) - 1
        q_lo = (nq * rank) // num_machines
        q_hi = (nq * (rank + 1)) // num_machines
        return int(qb[q_lo]), int(qb[q_hi])
    lo = (n * rank) // num_machines
    hi = (n * (rank + 1)) // num_machines
    return lo, hi
