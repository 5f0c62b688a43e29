#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py via dmlc-tracker).

Reference semantics: ``launch.py -n W [-s S] cmd...`` starts a tracker
that spawns scheduler + S servers + W workers with ``DMLC_*`` env vars
(reference tools/launch.py:64-80).  Here there is no scheduler — sync
jobs are pure SPMD workers over a jax.distributed coordination service,
and ``-s`` (when given) spawns REAL async parameter-server processes for
kvstore ``dist_async`` (see ``_server_env``).  Workers are wired through
the same DMLC-shaped env vars (read by
``mxnet_tpu.distributed.initialize``):

    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT   coordinator host:port
    DMLC_NUM_WORKER                        process count
    DMLC_WORKER_ID                         per-process id
    DMLC_ROLE=worker                       every process (no 'server')

``-s S`` starts S async parameter-server processes (kvstore
``dist_async``): the same command with ``DMLC_ROLE=server`` — importing
mxnet_tpu in that role enters the blocking server loop (reference:
python/mxnet/kvstore_server.py:28-75) — pinned to ``JAX_PLATFORMS=cpu``
so servers never touch an accelerator.  Every process gets
``MXT_SERVER_URIS`` (comma list of host:port) for worker→server dialing;
servers are torn down by the launcher once all workers exit.

Two launchers:

* ``--launcher local`` (default) — W processes on this machine.  With
  W > 1 this is the CPU test plane: the workers are pinned to
  ``JAX_PLATFORMS=cpu`` and a launch that names another platform is
  refused, because a chip belongs to one process (``_local_platform``).
* ``--launcher ssh`` — W processes spread round-robin over the hosts in
  ``-H/--hostfile`` (reference: tools/launch.py:64-80 ssh mode via
  dmlc-tracker), each started as ``ssh <host> 'cd <dir> && env DMLC_*=…
  cmd'``; the coordinator address defaults to this machine's IP so every
  remote worker dials back to one jax.distributed coordination service.
  ``--ssh-cmd`` swaps the transport binary (tests inject a local shim;
  ``ssh -o BatchMode=yes`` style options ride here too).

mpi/sge/yarn launchers are intentionally absent: on TPU pods the
platform's own process manager starts one process per host and
``initialize()`` auto-detects — see docs/design/kvstore.md.
"""
import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(args, coord_uri, port, wid):
    """The DMLC-shaped contract every worker reads
    (mxnet_tpu.distributed.initialize)."""
    env = {}
    env.update(e.split("=", 1) for e in args.env)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": coord_uri,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "DMLC_WORKER_ID": str(wid),
    })
    if getattr(args, "server_uris", None):
        env["MXT_SERVER_URIS"] = ",".join(args.server_uris)
    if getattr(args, "elastic", False):
        env.setdefault("MXNET_KVSTORE_ELASTIC", "1")
    if getattr(args, "mesh_uris", None):
        # hierarchical kvstore tier (MXNET_KVSTORE_HIERARCHY): one
        # in-host aggregation endpoint per host group, leader = the
        # group's lowest rank (membership.host_groups — consecutive
        # ranks share a host, which is exactly how the spawn loops
        # below fill slots)
        env["MXT_MESH_URIS"] = ",".join(args.mesh_uris)
        env.setdefault("MXNET_KVSTORE_HIERARCHY", "1")
        env.setdefault("MXNET_KVSTORE_WORKERS_PER_HOST",
                       str(args.workers_per_host))
    if getattr(args, "shm", None):
        # same-host follower->leader lane (mxnet_tpu/shmlane.py);
        # the knob also rides --env / the parent environment — this
        # flag just spells the common toggle
        env["MXNET_KVSTORE_SHM"] = args.shm
    return env


def _server_env(args, sid):
    """Env for one DMLC_ROLE=server process (kvstore dist_async backend,
    mxnet_tpu/kvstore_server.py).  JAX is pinned to CPU: a server doing
    tiny optimizer math must never claim a TPU (the reference gives
    servers no GPU context either)."""
    env = {}
    env.update(e.split("=", 1) for e in args.env)
    env.update({
        "DMLC_ROLE": "server",
        "DMLC_SERVER_ID": str(sid),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "MXT_SERVER_URIS": ",".join(args.server_uris),
        "JAX_PLATFORMS": env.get("JAX_PLATFORMS", "cpu"),
    })
    if getattr(args, "elastic", False):
        env.setdefault("MXNET_KVSTORE_ELASTIC", "1")
    return env


def _local_platform(args):
    """JAX_PLATFORMS for the workers of a local launch.

    A chip belongs to one process, and W local workers inherit one
    environment, so on a chip host every one of them would claim the
    same chips.  Several workers on one machine are therefore the CPU
    test plane (every tests/dist script runs there): they are pinned to
    the CPU, and a launch that asks for anything else is refused instead
    of left to contend.  One process per host drives that host's chips —
    a single local worker keeps whatever platform its environment
    names."""
    asked = dict(e.split("=", 1) for e in args.env).get(
        "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS"))
    if args.num_workers <= 1:
        return asked
    if asked is None:
        print("launch.py: %d local workers share this machine: pinning "
              "them to JAX_PLATFORMS=cpu (a chip belongs to one process)"
              % args.num_workers, file=sys.stderr, flush=True)
        return "cpu"
    if asked.split(",")[0].strip().lower() != "cpu":
        raise SystemExit(
            "launch.py: refusing to start %d local workers with "
            "JAX_PLATFORMS=%s: they would all claim the same chips.  Local "
            "multi-worker launches run on the CPU (JAX_PLATFORMS=cpu); on "
            "a chip host start one process, which drives every chip of "
            "the host." % (args.num_workers, asked))
    return asked


def _spawn_local(args, port):
    procs = []
    for wid in range(args.num_workers):
        env = dict(os.environ)
        env.update(_worker_env(args, "127.0.0.1", port, wid))
        if args.local_platform is not None:
            env["JAX_PLATFORMS"] = args.local_platform
        procs.append(subprocess.Popen(args.command, env=env))
    return procs


def _spawn_servers_local(args):
    procs = []
    for sid in range(args.num_servers):
        env = dict(os.environ)
        env.update(_server_env(args, sid))
        procs.append(subprocess.Popen(args.command, env=env))
    return procs


def _spawn_servers_ssh(args, slots):
    """Same port caveat as the worker coordinator (_spawn_ssh docstring):
    each server port is picked free on THIS machine and can in principle
    collide on the remote host that binds it — the server then dies with
    EADDRINUSE at import and the launcher fails the job; rerun."""
    procs = []
    wdir = args.remote_dir or os.getcwd()
    for sid in range(args.num_servers):
        host = slots[sid % len(slots)]
        envs = _server_env(args, sid)
        env_line = " ".join(f"{k}={shlex.quote(v)}"
                            for k, v in sorted(envs.items()))
        cmd_line = " ".join(shlex.quote(c) for c in args.command)
        remote = f"cd {shlex.quote(wdir)} && env {env_line} {cmd_line}"
        procs.append(subprocess.Popen(
            shlex.split(args.ssh_cmd) + [host, remote]))
    return procs


def _parse_hostfile(path):
    """Hosts with their slot counts.  Lines are ``host [slots=N]``
    (the dmlc-tracker hostfile shape); blank lines and ``#`` comments —
    indented or not — are skipped."""
    hosts = []
    with open(path) as f:
        for raw in f:
            ln = raw.strip()
            if not ln or ln.startswith("#"):
                continue
            parts = ln.split()
            slots = 1
            for tok in parts[1:]:
                if tok.startswith("slots="):
                    slots = max(1, int(tok.split("=", 1)[1]))
                else:
                    raise SystemExit(
                        f"launch.py: unrecognized hostfile token {tok!r} "
                        f"on line {raw!r} (expected 'host [slots=N]')")
            hosts.extend([parts[0]] * slots)
    return hosts


def _spawn_ssh(args, port):
    """reference: tools/launch.py:64-80 (ssh cluster via dmlc-tracker) —
    one ssh per worker, workers filling each host's slots in hostfile
    order (wrapping if -n exceeds total slots); env rides an ``env``
    prefix inside the remote shell line because ssh does not forward it.

    Worker 0 HOSTS the jax.distributed coordination service, so the
    coordinator address every worker dials must be worker 0's host —
    the first hostfile entry — not this launcher machine (which may not
    be in the cluster at all).  The port is picked here and can in
    principle collide on that host; rerun on collision."""
    slots = _parse_hostfile(args.hostfile)
    if not slots:
        raise SystemExit(f"launch.py: no hosts in {args.hostfile}")
    coord = args.coordinator_host or slots[0]
    wdir = args.remote_dir or os.getcwd()
    procs = []
    for wid in range(args.num_workers):
        host = slots[wid % len(slots)]
        envs = _worker_env(args, coord, port, wid)
        env_line = " ".join(f"{k}={shlex.quote(v)}"
                            for k, v in sorted(envs.items()))
        cmd_line = " ".join(shlex.quote(c) for c in args.command)
        remote = f"cd {shlex.quote(wdir)} && env {env_line} {cmd_line}"
        procs.append(subprocess.Popen(
            shlex.split(args.ssh_cmd) + [host, remote]))
    return procs


def main():
    ap = argparse.ArgumentParser(
        description="Launch a multi-process mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="number of async parameter-server processes "
                         "(kvstore 'dist_async'): the same command run "
                         "with DMLC_ROLE=server, pinned to CPU; 0 = "
                         "allreduce-only job (dist_sync needs no servers)")
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh"],
                    help="'local' spawns on this machine; 'ssh' spreads "
                         "workers over -H hosts (reference ssh mode); "
                         "mpi/sge/yarn do not apply to TPU pods")
    ap.add_argument("-H", "--hostfile",
                    help="ssh mode: file with one host per line")
    ap.add_argument("--ssh-cmd", default="ssh -tt",
                    help="ssh mode: transport command (options allowed, "
                         "e.g. 'ssh -tt -o BatchMode=yes'; -tt makes a "
                         "local terminate() reach the remote worker)")
    ap.add_argument("--coordinator-host", default=None,
                    help="ssh mode: coordination-service address every "
                         "worker dials (default: the FIRST hostfile "
                         "entry — worker 0 hosts the service)")
    ap.add_argument("--remote-dir", default=None,
                    help="ssh mode: working directory on each host "
                         "(default: this process's cwd)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE env for every worker")
    ap.add_argument("--workers-per-host", type=int, default=0,
                    help="hierarchical kvstore tier "
                         "(MXNET_KVSTORE_HIERARCHY): worker ranks per "
                         "host — consecutive ranks form one in-host "
                         "mesh group whose leader alone ships "
                         "gradients over the wire; allocates one mesh "
                         "endpoint (MXT_MESH_URIS) per group.  0 = "
                         "flat dist_async")
    ap.add_argument("--shm", choices=("auto", "on", "off"), default=None,
                    help="same-host shared-memory lane for the mesh "
                         "tier's follower->leader traffic "
                         "(MXNET_KVSTORE_SHM): auto (default) uses it "
                         "when the mesh endpoint is local, falling "
                         "back to loopback TCP otherwise; unset "
                         "leaves the workers' environment alone")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic membership (MXNET_KVSTORE_ELASTIC): a "
                         "parameter server exiting — even killed, even "
                         "server 0, the roster coordinator — no longer "
                         "fails the job; the survivors elect the "
                         "deterministic successor, rebuild the "
                         "membership ledger, re-stripe and hand state "
                         "off over the roster")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="command to run on every worker")
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.launcher == "ssh" and not args.hostfile:
        ap.error("--launcher ssh requires -H/--hostfile")
    if args.launcher == "local":
        # resolved (and possibly refused) BEFORE any server is started
        args.local_platform = _local_platform(args)
    # parameter servers (kvstore dist_async): pick their ports up front so
    # workers AND servers share one MXT_SERVER_URIS view
    sprocs = []
    args.server_uris = []
    if args.num_servers:
        if args.launcher == "ssh":
            slots = _parse_hostfile(args.hostfile)
            if not slots:
                raise SystemExit(f"launch.py: no hosts in {args.hostfile}")
            args.server_uris = [
                f"{slots[sid % len(slots)]}:{_free_port()}"
                for sid in range(args.num_servers)]
            sprocs = _spawn_servers_ssh(args, slots)
        else:
            args.server_uris = [f"127.0.0.1:{_free_port()}"
                                for _ in range(args.num_servers)]
            sprocs = _spawn_servers_local(args)

    # hierarchical tier: one mesh endpoint per host group, bound on the
    # group leader's host (local mode: loopback).  Allocated before the
    # spawn so every worker shares one MXT_MESH_URIS view, exactly like
    # MXT_SERVER_URIS above.
    args.mesh_uris = []
    if args.workers_per_host > 0:
        n_groups = -(-args.num_workers // args.workers_per_host)
        if args.launcher == "ssh":
            slots = _parse_hostfile(args.hostfile)
            args.mesh_uris = [
                "%s:%d" % (slots[(g * args.workers_per_host)
                                 % len(slots)], _free_port())
                for g in range(n_groups)]
        else:
            args.mesh_uris = ["127.0.0.1:%d" % _free_port()
                              for _ in range(n_groups)]

    port = _free_port()
    procs = _spawn_ssh(args, port) if args.launcher == "ssh" \
        else _spawn_local(args, port)

    def _kill_all(signum=None, frame=None):
        for p in procs + sprocs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, _kill_all)
    signal.signal(signal.SIGTERM, _kill_all)

    # poll ALL workers: the first nonzero exit kills the job immediately
    # (SPMD semantics — a worker that dies before joining the coordination
    # service would otherwise leave the rest blocked in initialize()).
    # A server dying while workers live is likewise fatal: every push to
    # its key shard would stall the workers.
    import time
    rc = 0
    live = list(procs)
    slive = list(sprocs)
    while live:
        for p in list(live):
            code = p.poll()
            if code is None:
                continue
            live.remove(p)
            if code != 0 and rc == 0:
                rc = code
                _kill_all()
        for p in list(slive):
            code = p.poll()
            if code is None:
                continue
            slive.remove(p)
            # exit 0 = the documented kStopServer shutdown (a worker's
            # kv.close(stop_servers=True)) — benign; only a CRASHED
            # server (nonzero) fails the job.  Under --elastic ANY dead
            # server — the coordinator included — is a MEMBERSHIP
            # event, not a job failure: the survivors evict it from the
            # roster (slot 0's death seats the deterministically
            # elected successor, docs/ROBUSTNESS.md coordinator
            # failover), re-derive striping and hand its state off (the
            # workers' own exit codes still decide the job).  Every
            # server dying leaves the workers to fail on their own
            # exhausted retry budgets, which sets rc.
            if code != 0 and rc == 0:
                sid = sprocs.index(p)
                if args.elastic:
                    print("launch.py: server %d exited %d; elastic job "
                          "continues on the surviving roster%s"
                          % (sid, code,
                             " (coordinator died: successor takes over)"
                             if sid == 0 else ""), flush=True)
                else:
                    rc = code
                    _kill_all()
        time.sleep(0.1)
    # workers done: tear the servers down (the reference's scheduler sends
    # kStopServer at job end; here the launcher owns teardown)
    for p in sprocs:
        if p.poll() is None:
            p.terminate()
    for p in sprocs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    sys.exit(rc)


if __name__ == "__main__":
    main()
