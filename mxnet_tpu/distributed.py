"""Multi-host bootstrap: the TPU-native replacement for the reference's
parameter-server bring-up.

The reference boots a cluster with dmlc-tracker: ``tools/launch.py`` spawns
scheduler + server + worker processes and wires them with ``DMLC_*``
environment variables (reference: tools/launch.py:64-80,
python/mxnet/kvstore_server.py:28-75, src/kvstore/kvstore_dist.h:51-61).
On TPU there are no servers and no scheduler — every process is a worker
running the same SPMD program; bootstrap is ``jax.distributed.initialize``
(coordination service + PJRT), and gradient aggregation is an allreduce
over the global mesh (ICI intra-slice, DCN across slices).

``initialize()`` reads the same env-var shapes the reference's tracker
sets, so ``tools/launch.py`` here mirrors the reference CLI:

* ``DMLC_PS_ROOT_URI`` / ``DMLC_PS_ROOT_PORT`` → coordinator address
* ``DMLC_NUM_WORKER``                          → number of processes
* ``DMLC_WORKER_ID``                           → this process's id

(Native JAX deployments can instead rely on jax.distributed's own
auto-detection — TPU pods populate these from the metadata server.)
"""
from __future__ import annotations

import atexit
import os
from typing import Optional

from .base import MXNetError

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> None:
    """Bootstrap the multi-process runtime (idempotent).

    Arguments default from the DMLC-shaped environment set by
    ``tools/launch.py`` (or a TPU pod's native metadata — in that case call
    with no arguments and jax.distributed auto-detects everything).
    """
    global _initialized
    if _initialized:
        return
    import jax
    env = os.environ
    if coordinator_address is None and "DMLC_PS_ROOT_URI" in env:
        coordinator_address = "%s:%s" % (
            env["DMLC_PS_ROOT_URI"], env.get("DMLC_PS_ROOT_PORT", "9091"))
    if num_processes is None and "DMLC_NUM_WORKER" in env:
        num_processes = int(env["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in env:
        process_id = int(env["DMLC_WORKER_ID"])
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    _initialized = True
    atexit.register(shutdown)


def is_initialized() -> bool:
    return _initialized


def rank() -> int:
    """This process's id (reference: KVStore::get_rank, kvstore_dist.h:98)."""
    import jax
    return jax.process_index()


def size() -> int:
    """Number of processes (reference: get_group_size, kvstore_dist.h:100)."""
    import jax
    return jax.process_count()


def barrier(name: str = "mxnet_tpu_barrier") -> None:
    """Block until every process arrives (reference: Postoffice::Barrier)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def allreduce_sum(value):
    """Sum a per-process host value across all processes; every process
    gets the total.  The kvstore 'dist_sync' aggregation primitive."""
    import jax
    import numpy as np
    if jax.process_count() == 1:
        return np.asarray(value)
    from jax.experimental import multihost_utils
    from . import profiler as _prof
    arr = np.asarray(value)
    # per-process contribution to the gather — the host-collective twin
    # of the socket transport's sent/recv byte counters
    _prof.record_channel_bytes("allgather", int(arr.nbytes))
    return np.asarray(
        multihost_utils.process_allgather(arr)).sum(axis=0)


def broadcast_from_root(value):
    """Every process gets rank 0's value (reference: dist kvstore init —
    the first worker's init value is authoritative,
    kvstore_dist_server.h DataHandleDefault init path)."""
    import jax
    import numpy as np
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils
    from . import profiler as _prof
    arr = np.asarray(value)
    _prof.record_channel_bytes("allgather", int(arr.nbytes))
    # process_allgather lands on host in every process; rank 0's slice is
    # the broadcast value (broadcast_one_to_all returns a global-mesh
    # jax.Array that host code cannot read directly)
    return np.asarray(
        multihost_utils.process_allgather(arr))[0]


# Liveness sources: objects exposing num_dead_nodes() (dist_async
# kvstores register their heartbeat monitors here).  Weakrefs — a
# forgotten store must not pin itself alive or keep reporting.
_dead_node_sources: list = []


def _register_dead_node_source(obj) -> None:
    import weakref
    _dead_node_sources.append(weakref.ref(obj))


def _live_sources():
    """The registry's still-alive objects, pruning dead weakrefs as a
    side effect — the one deref/prune loop every aggregate reads
    through (num_dead_nodes / roster_generation /
    coordinator_failovers)."""
    for ref in list(_dead_node_sources):
        obj = ref()
        if obj is None:
            try:
                _dead_node_sources.remove(ref)
            except ValueError:
                pass
            continue
        yield obj


def num_dead_nodes() -> int:
    """Reference parity: KVStore::get_num_dead_node (kvstore.h:328).

    Two failure models meet here.  The SPMD collective path has no
    partial-failure mode — the coordination-service heartbeat turns any
    process death into a job-wide error, so that side contributes zero
    by construction (recovery is restart-from-checkpoint,
    docs/design/failure_recovery.md).  The ``dist_async`` parameter-
    server path DOES fail partially: each worker↔server channel runs a
    low-rate heartbeat, and every open dist_async kvstore registers
    itself here — a server whose channel has gone silent past
    ``MXNET_KVSTORE_HEARTBEAT_TIMEOUT`` counts as a dead node."""
    total = 0
    for obj in _live_sources():
        try:
            total += obj.num_dead_nodes()
        except Exception:  # noqa: BLE001 — a broken source is not a death
            pass
    return total


def roster_generation() -> int:
    """The highest elastic-membership roster generation any open
    dist_async store in this process has converged onto (0 for a static
    roster / no elastic stores).  Rides the same weakref registry as
    ``num_dead_nodes`` — a store that has been GC'd stops reporting.
    Job-level liveness in one read: a generation that moved means the
    cluster lost or gained members and this process has already
    re-derived its striping against the survivors."""
    best = 0
    for obj in _live_sources():
        gen = getattr(obj, "_roster_gen", None)
        if isinstance(gen, int) and gen > best:
            best = gen
    return best


def coordinator_failovers() -> int:
    """Coordinator successions any open dist_async store in this
    process has ridden through (0 = the bootstrap coordinator still
    leads).  The companion read to :func:`roster_generation`: a
    generation that moved says the roster churned; a failover count
    that moved says the churn took the COORDINATOR itself — the elastic
    layer elected a successor, rebuilt the ledger and kept going
    (profiler gauges ``kvstore.coordinator_slot`` and
    ``kvstore.failover_rebuild_s`` carry the detail).  Same weakref
    registry as ``num_dead_nodes``."""
    total = 0
    for obj in _live_sources():
        n = getattr(obj, "_failovers", None)
        if isinstance(n, int):
            total += n
    return total


def cluster_stats(compact: bool = False) -> dict:
    """One dict of cluster-wide observability counters
    (docs/OBSERVABILITY.md): this process's own profiler snapshot under
    ``workers[<rank>]``, every live parameter server's ``("stats",)``
    reply under ``servers[<uri>]`` — swept through the same weakref
    registry as :func:`num_dead_nodes`, so a GC'd store stops being
    consulted — and ``stats_bank``, the newest-beat-wins merge of the
    servers' last-known-counters banks, which still names members that
    have DIED (the bank outlives eviction, like the elastic state
    snapshots).  ``compact=True`` trims each entry to the transport
    families.

    A server whose channel fails mid-sweep is skipped rather than
    failing the whole sweep: its last-known counters are usually still
    in the surviving servers' banks — that is the bank's whole point."""
    from . import profiler as _prof
    from . import tracing as _tr
    _role, rank = _tr.role_rank()   # the shared DMLC-label derivation
    out: dict = {
        "workers": {str(rank): _prof.snapshot(compact=compact)},
        "servers": {},
        "stats_bank": {},
    }
    for obj in _live_sources():
        conns = getattr(obj, "_conns", None)
        server_stats = getattr(obj, "server_stats", None)
        if conns is None or server_stats is None:
            continue
        if getattr(obj, "_closed", False):
            # a closed store lingering until gc must not be swept: its
            # channels answer nothing (request() fails fast post-close,
            # but skipping is cheaper than 2N raised errors)
            continue
        for i, c in enumerate(list(conns)):
            uri = str(getattr(c, "_uri", i))
            if uri in out["servers"]:
                continue
            try:
                st = server_stats(i)
            except MXNetError:
                continue   # dead mid-sweep: the bank below may cover it
            if not isinstance(st, dict):
                continue
            bank = st.pop("stats_bank", None) or {}
            if compact:
                st = {k: st[k] for k in ("channel", "channel_bytes",
                                         "wire", "server", "health")
                      if k in st}
            out["servers"][uri] = st
            for u, entry in bank.items():
                if not isinstance(entry, dict):
                    continue
                prev = out["stats_bank"].get(u)
                if prev is None or int(entry.get("beat_seq", 0)) >= \
                        int(prev.get("beat_seq", 0)):
                    out["stats_bank"][u] = entry
    return out


def cluster_health() -> dict:
    """One cluster-wide health verdict (docs/OBSERVABILITY.md health
    section): per-node ``OK``/``DEGRADED``/``CRITICAL`` statuses — this
    process's own, every live server's (from the health block its
    ``("stats",)`` reply carries), and the banked last-known status of
    members only the stats bank still remembers — rolled up to the
    WORST observed.  A bank member absent from the live server sweep is
    listed under ``dead`` and floors the cluster at DEGRADED (it was a
    beating member once; now nobody answers for it), as does a nonzero
    local ``num_dead_nodes()``.  Peer entries without a self-reported
    health block are evaluated against the local SLO rule thresholds
    (``health.evaluate``) so an old or minimal snapshot still gets a
    verdict instead of a silent OK.  Self-reported verdicts carry a
    wall-clock ``ts`` stamp: one older than ``MXNET_HEALTH_STALE_S``
    no longer earns an OK (``health.discount_stale``) — the discounted
    nodes are listed under ``stale``."""
    from . import health as _health
    order = {"OK": 0, "DEGRADED": 1, "CRITICAL": 2}
    # compact sweep: the health block (and the channel/wire families
    # the evaluate() fallback reads) ride the compact form — full
    # snapshots would ship every server's latency tables and event
    # rings per poll for nothing
    stats = cluster_stats(compact=True)
    nodes: dict = {}
    dead: list = []
    stale: list = []
    worst = "OK"

    def verdict(snap, name=None):
        h = snap.get("health") if isinstance(snap, dict) else None
        if isinstance(h, dict) and h.get("status") in order:
            st = h["status"]
            # discount a stale verdict: a banked block whose ts stamp
            # is past MXNET_HEALTH_STALE_S no longer earns an OK — the
            # member went silent, and silence is not health
            age = _health.verdict_age_s(h)
            discounted = _health.discount_stale(st, age)
            if discounted != st and name is not None:
                stale.append(name)
            return discounted
        st, _failed = _health.evaluate(snap if isinstance(snap, dict)
                                       else {})
        return st

    def fold(name, snap):
        nonlocal worst
        st = verdict(snap, name=name)
        nodes[name] = st
        if order[st] > order[worst]:
            worst = st

    for rank, snap in stats["workers"].items():
        fold("worker-%s" % rank, snap)
    for uri, snap in stats["servers"].items():
        fold("server-%s" % uri, snap)
    live_uris = set(stats["servers"])
    for uri, entry in stats["stats_bank"].items():
        if uri in live_uris:
            continue
        # a member the bank remembers but the live sweep cannot reach:
        # dead (or partitioned).  Its last-known status is FORENSICS
        # (shown per node), never a live verdict — a stale banked
        # CRITICAL must not escalate a repaired cluster forever, so a
        # dead member contributes exactly the DEGRADED floor
        dead.append(uri)
        nodes["dead-%s" % uri] = verdict(entry, name="dead-%s" % uri)
        if order[worst] < order["DEGRADED"]:
            worst = "DEGRADED"
    n_dead = num_dead_nodes()
    if n_dead and order[worst] < order["DEGRADED"]:
        worst = "DEGRADED"
    return {"status": worst, "nodes": nodes, "dead": sorted(dead),
            "stale": sorted(stale), "num_dead_nodes": n_dead}


def shutdown() -> None:
    global _initialized
    if not _initialized:
        return
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — already torn down at interpreter exit
        pass
    _initialized = False
