#!/usr/bin/env bash
# CI entry point (reference: Jenkinsfile:52-99 build+test matrix).
# Runs the full suite on the virtual 8-device CPU mesh, the multichip
# dryrun and the multi-process dist tests.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== analysis gate: framework-aware lint + knob registry (docs/ANALYSIS.md)"
# The invariants earlier PRs paid for — sync-free hot path, allowlisted
# unpickling, acyclic lock order, declared+documented env knobs,
# crash-propagating threads — enforced at the SOURCE level: any
# unannotated finding (or a knob missing from the registry/ROBUSTNESS
# table) fails here, before a single test runs.  Same check runs
# in-process in tests/test_analysis.py; this invocation pins the entry
# point the way a developer runs it.
JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis --strict

echo "== analysis gate: generated doc tables in sync (--check drift mode)"
# The knob table in docs/ROBUSTNESS.md and the wire-protocol op table
# in docs/PROTOCOL.md are GENERATED projections; a knob or wire op
# added without regenerating them fails HERE instead of silently
# rotting the docs (regenerate: --knob-table / --protocol-table).
JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis --check

echo "== interleaving explorer gate (PCT schedules + seeded-bug detection)"
# The systematic-interleaving surface (docs/ANALYSIS.md explorer
# section): every real distributed-plane scenario must survive a
# small-N seeded schedule sweep race-, deadlock- and starvation-clean
# (the full N=20 acceptance sweep runs inside the test suite below,
# concurrently), and the explorer must PROVE it still finds bugs: the
# planted ABBA deadlock and check-then-act race must fail the run
# (nonzero exit) leaving a journal that --replay reproduces.
# Time-boxed: a scheduler regression presents as a hang.
rm -rf /tmp/_sched_ci && mkdir -p /tmp/_sched_ci
for sc in kill_replay handoff failover replan mesh_fanin shm_ring \
          acceptor_park; do
  JAX_PLATFORMS=cpu timeout -k 10 240 \
      python -m mxnet_tpu.analysis --explore "$sc" --schedules 3 \
      --seed 0 --journal-dir /tmp/_sched_ci/"$sc"
done
for bug in bug_deadlock bug_atomicity; do
  if JAX_PLATFORMS=cpu timeout -k 10 240 \
      python -m mxnet_tpu.analysis --explore "$bug" --schedules 25 \
      --seed 0 --journal-dir /tmp/_sched_ci/"$bug"; then
    echo "EXPLORER GATE VIOLATION: planted $bug was NOT found" >&2
    exit 1
  fi
  journal=$(ls /tmp/_sched_ci/"$bug"/*.jsonl | head -1)
  if [ -z "$journal" ]; then
    echo "EXPLORER GATE VIOLATION: $bug left no journal artifact" >&2
    exit 1
  fi
  # the journal must REPLAY to the same failure (nonzero again)
  if JAX_PLATFORMS=cpu timeout -k 10 240 \
      python -m mxnet_tpu.analysis --replay "$journal" \
      --journal-dir /tmp/_sched_ci/replay-"$bug"; then
    echo "EXPLORER GATE VIOLATION: $bug journal replayed clean" >&2
    exit 1
  fi
done

echo "== unit + integration suite (8-device CPU mesh via tests/conftest.py)"
# -m "" overrides pytest.ini's default "not slow": CI runs everything.
# test_run_steps.py is excluded here because the dedicated gate below
# runs the whole file — double-running the heaviest new file buys no
# coverage.
python -m pytest tests/ -q --durations=10 -m "" \
    --ignore=tests/test_run_steps.py \
    --ignore=tests/test_sync_free.py

echo "== tier-1: K-step scan == K eager steps (CPU bit-equivalence gate)"
# The multi-step driver's correctness is provable WITHOUT a chip: the
# scanned program must reproduce K eager fused steps bit-for-bit on the
# CPU backend.  Kept as its own invocation so a pytest.ini / conftest
# change can't silently drop it from the gate.
# -m "" so the slow-marked equivalence variants run here too
JAX_PLATFORMS=cpu python -m pytest tests/test_run_steps.py -q -m ""

echo "== sync-count regression gate (sync-free training loop)"
# A short CPU fit() must record <= N/frequent + 2 host syncs per epoch
# (device-resident metrics; callbacks are the only sync points) while
# the legacy host-metric path is pinned at >= 1 sync PER BATCH — both
# live in tests/test_sync_free.py, run as its own invocation so a
# pytest.ini / conftest change can't silently drop the gate.  A
# regression that re-grows a per-batch device->host sync fails HERE,
# on CPU, instead of only showing up as step-time jitter on a chip.
JAX_PLATFORMS=cpu python -m pytest tests/test_sync_free.py -q -m ""

echo "== fault-injection smoke (dist_async kill-and-recover)"
# The transport recovery path (reconnect + replay + server dedup,
# docs/ROBUSTNESS.md) must not rot: sever worker 0's channel mid-push
# under the real launcher and require the exact post-barrier total —
# a lost push or a double-applied replay both fail the arithmetic.
# Time-boxed: a recovery regression typically presents as a HANG.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    python tests/dist/dist_fault_injection.py

echo "== fault-injection smoke: pipelined window + 2-bit compression"
# Same kill-and-recover arithmetic, now over the PIPELINED wire: 8
# envelopes in flight and every push 2-bit quantized (the smoke script
# simulates the deterministic quantizer to compute the exact expected
# total).  A replay that loses an envelope, double-applies one, or
# corrupts the compressed frame breaks the exact number.  Time-boxed:
# a window-replay regression typically presents as a HANG.
JAX_PLATFORMS=cpu MXNET_KVSTORE_WINDOW=8 \
    MXNET_KVSTORE_COMPRESSION=2bit \
    MXNET_KVSTORE_COMPRESSION_THRESHOLD=1.0 timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    python tests/dist/dist_fault_injection.py

echo "== fault-injection smoke: binary wire codec forced (v2 frames replayed)"
# ISSUE 16's transport gate: the same sever-replay-dedup arithmetic
# with MXNET_KVSTORE_CODEC=binary forced on every process — the
# reconnect re-runs the codec hello BEFORE replaying the unacked
# window, so the replayed envelopes ride the new binary frame.  A
# framing regression presents as a hang in the receive loop or a
# broken total.  (launch.py children inherit the launcher's env.)
JAX_PLATFORMS=cpu MXNET_KVSTORE_CODEC=binary timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    python tests/dist/dist_fault_injection.py

echo "== mixed-version interop smoke (pickle-pinned server, binary workers)"
# The negotiation contract across real process boundaries: the server
# pins MXNET_KVSTORE_CODEC=pickle (what a pre-codec peer looks like on
# the wire — hellos answered with version 0) while the workers force
# =binary; every connection must settle on pickle framing and the
# exact SGD total must survive.  The role-dependent env pin lives in
# the script itself.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    python tests/dist/dist_codec_interop.py

echo "== elastic-membership smoke (SIGKILL a server mid-epoch, no restart)"
# The roster must ACT on the liveness/striping/replay primitives
# (docs/ROBUSTNESS.md elastic membership): server 1 is REALLY SIGKILLed
# after serving exactly the last ack of round 2 (the count is derived
# from the wire protocol — dist_elastic_membership.expected_kill_acks
# documents the arithmetic and prints it under MXT_PRINT_KILL_ACKS).
# The surviving roster evicts it, re-stripes, hands state off from the
# workers' sync-point caches and re-pushes the orphaned gradients; the
# job must COMPLETE WITHOUT RESTART with final weights BIT-IDENTICAL to
# the static-roster golden.  Time-boxed: an elastic regression
# typically presents as a hang in the renegotiated barrier.
kill_acks=$(MXT_PRINT_KILL_ACKS=1 python tests/dist/dist_elastic_membership.py)
# The gate now ALSO runs traced (MXNET_TRACE=1, near-zero overhead by
# contract): after the job survives, the per-process span journals must
# merge into ONE chrome trace in which the handoff is a span with its
# three protocol phases as children, hanging off the worker-side
# kv.repair span, with cross-process flow arrows into the surviving
# servers — the ISSUE 12 acceptance timeline (docs/OBSERVABILITY.md).
# The SIGKILLed server's journal is torn mid-append by design; the
# merge must tolerate it.
rm -rf /tmp/_trace_elastic /tmp/_health_elastic
mkdir -p /tmp/_trace_elastic /tmp/_health_elastic
JAX_PLATFORMS=cpu MXNET_TRACE=1 MXNET_TRACE_DIR=/tmp/_trace_elastic \
    timeout -k 10 240 \
    python tools/launch.py --elastic -n 2 -s 2 \
    --env MXNET_FI_KILL_PROCESS_AFTER="$kill_acks" \
    --env MXNET_FI_ONLY_SERVER=1 \
    --env MXNET_HEALTH_DIR=/tmp/_health_elastic \
    python tests/dist/dist_elastic_membership.py
python tools/trace_merge.py --spans /tmp/_trace_elastic \
    -o /tmp/_trace_elastic_merged.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
m = json.load(open("/tmp/_trace_elastic_merged.json"))
evs = [e for e in m["traceEvents"] if e.get("ph") == "X"]
by_span = {e["args"]["span"]: e for e in evs}
handoffs = [e for e in evs if e["name"] == "kv.handoff"]
assert handoffs, "merged elastic trace has no kv.handoff span"
# every handoff carries its three protocol phases as children ...
for h in handoffs:
    kids = {e["name"] for e in evs
            if e["args"].get("parent") == h["args"]["span"]}
    assert {"handoff.values", "handoff.states",
            "handoff.repush"} <= kids, (h["args"]["span"], kids)
# ... and at least one hangs off a worker-side kv.repair span.  (A
# worker that discovers the bump at a barrier instead of on a failed
# channel parents its handoff under kv.refresh — legal; but the kill
# lands mid-round with pushes in flight to the doomed server, so SOME
# worker always takes the channel-failure repair path.)
parents = {h["args"]["span"]:
           (by_span.get(h["args"].get("parent")) or {}).get("name")
           for h in handoffs}
assert set(parents.values()) <= {"kv.repair", "kv.refresh"}, parents
assert "kv.repair" in parents.values(), parents
traces = {e["args"]["trace"] for e in handoffs}
flows = [e for e in m["traceEvents"] if e.get("cat") == "flow"
         and e.get("ph") == "f" and e["id"].split(":")[0] in traces]
assert flows, "handoff trace has no cross-process flow"
print("elastic trace OK: handoff span + 3 phases under kv.repair, "
      "%d flows in its trace" % len(flows))
PY
# The same run's flight-recorder bundles feed the postmortem (ISSUE 13):
# the SIGKILLed server left NO bundle — the report must reconstruct the
# death from the survivors' bundles: who (server 1, by uri), the repair
# phase in flight, and witness health events from >= 1 survivor.
python tools/postmortem.py /tmp/_health_elastic \
    --trace-dir /tmp/_trace_elastic -o /tmp/_pm_elastic.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
r = json.load(open("/tmp/_pm_elastic.json"))
dead = [d for d in r["dead"] if d["shape"] == "sigkill"]
assert len(dead) == 1, r["dead"]
d = dead[0]
assert (d["role"], d["rank"]) == ("server", "1"), d
assert d["uri"], d
assert d["named_by"], "no survivor named the dead server"
assert len(d["witness_events"]) >= 1, d
assert d["repair_phases"], "no repair phases reconstructed"
assert d["phase_in_flight"] is not None, d
print("postmortem OK: %s-%s (%s) died during %s; named by %s"
      % (d["role"], d["rank"], d["uri"], d["phase_in_flight"],
         ", ".join(d["named_by"])))
PY

echo "== coordinator-failover smoke (SIGKILL server 0 mid-epoch, no restart)"
# Same arithmetic contract, but the SIGKILL now lands on the
# COORDINATOR itself — the death PR 7 still fail-fasted on.  The
# surviving workers elect the deterministic successor
# (membership.elect_successor — pure roster arithmetic, no votes),
# server 1 verifies the death and rebuilds the ledger at
# max(reported)+1, the idempotent bseq barrier retries absorb whichever
# replies died with server 0, and the job must COMPLETE WITHOUT RESTART
# bit-identical to the static-roster golden.  MXNET_FI_ONLY_COORDINATOR
# composes with the server-id filter so the plan names the ROLE, not
# just the id.  Time-boxed: a succession regression presents as a hang
# in the retried barrier.
kill_acks0=$(MXT_PRINT_KILL_ACKS=1 MXT_KILL_SERVER=0 \
    python tests/dist/dist_elastic_membership.py)
rm -rf /tmp/_health_failover && mkdir -p /tmp/_health_failover
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py --elastic -n 2 -s 2 \
    --env MXNET_FI_KILL_PROCESS_AFTER="$kill_acks0" \
    --env MXNET_FI_ONLY_SERVER=0 \
    --env MXNET_FI_ONLY_COORDINATOR=1 \
    --env MXT_KILL_SERVER=0 \
    --env MXNET_HEALTH_DIR=/tmp/_health_failover \
    python tests/dist/dist_elastic_membership.py
# This run is UNTRACED (no MXNET_TRACE): the postmortem must
# reconstruct the coordinator's death from crash bundles ALONE —
# proving the flight recorder independent of full tracing (the ISSUE 13
# acceptance's second half).  Who: server 0, the coordinator; the
# successor's own bundle records the failover it ran.
python tools/postmortem.py /tmp/_health_failover -o /tmp/_pm_failover.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
r = json.load(open("/tmp/_pm_failover.json"))
dead = [d for d in r["dead"] if d["shape"] == "sigkill"]
assert len(dead) == 1, r["dead"]
d = dead[0]
assert (d["role"], d["rank"]) == ("server", "0"), d
assert d["named_by"], "no survivor named the dead coordinator"
assert len(d["witness_events"]) >= 1, d
assert d["repair_phases"], d
# the successor (server 1) survived, recorded the succession, and its
# bundle carries the failover evidence even with tracing fully off
s1 = r["survivors"].get("server-1")
assert s1 is not None, r["survivors"]
assert any(e["kind"] == "failover" for e in d["witness_events"]) or \
    "server-1" in d["named_by"], d
print("postmortem OK (MXNET_TRACE=0): coordinator %s-%s died during %s;"
      " witnesses: %s" % (d["role"], d["rank"], d["phase_in_flight"],
                          ", ".join(d["named_by"])))
PY

echo "== row-sparse wire smoke (1% density <= 5% of dense bytes, bit-identical)"
# ISSUE 19's wire gate under the real launcher: two workers push the
# same dyadic row-sparse gradients twice against two striped servers —
# densified (the baseline) and as RowSparsePayload frames.  Both tables
# must EQUAL the analytic golden bit-for-bit while the sparse pass
# moves <= 5% of the dense pass's bytes.  Time-boxed: a sparse-wire
# regression presents as a broken inequality or a diverged table.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 2 -s 2 \
    python tests/dist/dist_sparse_embed.py

echo "== row-sparse restripe smoke (SIGKILL a server mid-job, exact row ranges)"
# The elastic machinery under SPARSE traffic: server 1 is REALLY
# SIGKILLed at a beat boundary mid-push-stream (beat-seq kill: ack
# arithmetic is density-dependent for sparse frames, the beat loop is
# not), taking its row range with it.  The roster must evict it,
# re-derive the row-range striping and finish WITHOUT RESTART with the
# bit-identical table — a mis-moved row range or a lost sparse push
# breaks equality.  Time-boxed: a restripe regression presents as a
# hang in the repair.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py --elastic -n 2 -s 2 \
    --env MXNET_KVSTORE_HEARTBEAT_INTERVAL=0.5 \
    --env MXNET_KVSTORE_HEARTBEAT_TIMEOUT=2.0 \
    --env MXNET_FI_KILL_ON_BEAT_SEQ=4 \
    --env MXNET_FI_ONLY_SERVER=1 \
    --env MXT_SPARSE_KILL=1 \
    python tests/dist/dist_sparse_embed.py

echo "== fused-dist smoke (K-step scan over the dist_async wire, overlapped)"
# The two headline wins finally compose (ISSUE 10):
# run_steps on update-on-kvstore drives the chunked scanned driver — one
# dispatch per chunk — with the grad-push/weight-pull round overlapped
# behind the next chunk's compute.  Two workers train eager vs fused
# (staleness 0 and 1) against one server; constant integer gradients x a
# power-of-two lr make all three runs BIT-IDENTICAL to the analytic
# golden (convergence equivalence), and the launcher-armed server ack
# delay makes the overlap measurable: wire_wait_ms of the staleness-1
# run must sit STRICTLY below the staleness-0 (unoverlapped) baseline,
# overlap_pct strictly above.  The in-process twins (bit-exact staleness
# goldens, dispatch pins, mid-window kill replay) run in tier-1
# (tests/test_fused_dist.py).  Time-boxed: an overlap regression
# presents as a failed inequality, a driver regression as a hang.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    --env MXNET_FI_DELAY_ACK_MS=10 \
    python tests/dist/dist_fused_runsteps.py

echo "== hierarchical kvstore smoke (in-mesh reduce + per-host wire shipping)"
# ISSUE 14's tentpole gate: two workers forming ONE host group
# (--workers-per-host 2) train flat then hierarchical through the fused
# driver.  Both runs must land BIT-IDENTICAL on the same analytic
# golden (summed SGD == sequential pushes, exact dyadics), the server's
# own byte counters must show the hierarchy phase's wire at <= 60% of
# the flat phase (the >= 40% acceptance drop), and the follower's
# gradients must show up in the new "ici_*" counter family instead of
# "sent".  Runs traced: the merged timeline must show the new tier —
# kv.mesh_reduce and kv.leader_ship spans descending from a
# fused.chunk.  Time-boxed: a fan-in regression presents as a hang, a
# byte regression as a failed inequality.
# MXNET_KVSTORE_SHM=0 pins this run to loopback TCP: it is the byte
# and send_syscalls baseline the shm gates below compare against
rm -rf /tmp/_trace_hier && mkdir -p /tmp/_trace_hier
JAX_PLATFORMS=cpu MXNET_TRACE=1 MXNET_TRACE_DIR=/tmp/_trace_hier \
    timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 --workers-per-host 2 --shm off \
    python tests/dist/dist_hier_smoke.py
python tools/trace_merge.py --spans /tmp/_trace_hier \
    -o /tmp/_trace_hier_merged.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
m = json.load(open("/tmp/_trace_hier_merged.json"))
evs = [e for e in m["traceEvents"] if e.get("ph") == "X"]
by_span = {e["args"]["span"]: e for e in evs}
def ancestors(e):
    seen = set()
    while e is not None and e["args"].get("parent") not in seen:
        p = e["args"].get("parent")
        seen.add(p)
        e = by_span.get(p)
        if e is not None:
            yield e["name"]
for name in ("kv.mesh_reduce", "kv.leader_ship"):
    spans = [e for e in evs if e["name"] == name]
    assert spans, f"merged hierarchy trace has no {name} span"
    assert any("fused.chunk" in set(ancestors(s)) for s in spans), \
        f"{name} never descends from a fused.chunk span"
assert any(e["name"] == "kv.wire_wait" and e["args"].get("mesh")
           for e in evs if e.get("args")), \
    "no follower mesh wire_wait span"
print("hier trace OK: mesh_reduce + leader_ship under fused.chunk")
PY

echo "== shm-lane smoke (4 workers/host: follower payload off the sockets)"
# ISSUE 18's tentpole gate: the SAME smoke, now five ranks deep in one
# host group with the shared-memory lane forced on.  Every rank must
# land bit-identical on the analytic golden (concurrent follower
# deposits through the leader's acceptor pool == sequential), each
# follower's gradient frames must ride the "shm_*" counter family with
# the socket ici payload down to handshake residue (asserted inside
# the smoke), and steady-state frames cost zero socket syscalls.
timeout -k 10 300 \
    python tools/launch.py -n 4 -s 1 --workers-per-host 4 --shm on \
    python tests/dist/dist_hier_smoke.py

echo "== shm-lane wedge fallback (leader stops draining; TCP replay, zero failed steps)"
# MXNET_FI_SHM_WEDGE_AFTER=6 wedges the leader's ring drain mid-run;
# each follower's stall watchdog (tightened to 1s) must mark its lane
# dead and fail over to TCP through the ordinary reconnect+replay
# path: the run completes every step bit-identical and the follower
# records a kvstore.shm_fallback event (asserted inside the smoke).
timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 --workers-per-host 2 --shm on \
    --env MXNET_FI_SHM_WEDGE_AFTER=6 \
    --env MXNET_KVSTORE_SHM_STALL_S=1 \
    python tests/dist/dist_hier_smoke.py

echo "== elastic-fused smoke (SIGKILL a server mid-drive of the chunked driver)"
# The fused x elastic composition (ISSUE 14's second half): a single
# worker drives K steps through executor.drive_chunked_dist with a
# striped weight; server 1 is REALLY SIGKILLed right after serving the
# first push of chunk 2 (deterministic ack arithmetic in the script),
# leaving the chunk's second push and its pull round unserved.  The
# push leg must repair+re-route, the in-flight _PullHandle must REPLAN
# its unserved stripes against the survivor's layout, and the job must
# complete with NO eager fallback (one dispatch per chunk, pinned)
# bit-identical to the static-roster golden.  Runs traced: the merged
# timeline must carry a kv.replan instant under a kv.repair span.
# Time-boxed: a replan regression presents as a hang in wait().
kill_acks_f=$(MXT_PRINT_KILL_ACKS=1 python tests/dist/dist_elastic_fused.py)
rm -rf /tmp/_trace_efused && mkdir -p /tmp/_trace_efused
JAX_PLATFORMS=cpu MXNET_TRACE=1 MXNET_TRACE_DIR=/tmp/_trace_efused \
    timeout -k 10 240 \
    python tools/launch.py --elastic -n 1 -s 2 \
    --env MXNET_FI_KILL_PROCESS_AFTER="$kill_acks_f" \
    --env MXNET_FI_ONLY_SERVER=1 \
    python tests/dist/dist_elastic_fused.py
python tools/trace_merge.py --spans /tmp/_trace_efused \
    -o /tmp/_trace_efused_merged.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
m = json.load(open("/tmp/_trace_efused_merged.json"))
evs = [e for e in m["traceEvents"] if e.get("ph") == "X"]
by_span = {e["args"]["span"]: e for e in evs}
replans = [e for e in evs if e["name"] == "kv.replan"]
assert replans, "merged elastic-fused trace has no kv.replan instant"
parents = {(by_span.get(e["args"].get("parent")) or {}).get("name")
           for e in replans}
assert "kv.repair" in parents, parents
print("elastic-fused trace OK: %d kv.replan instants under kv.repair"
      % len(replans))
PY

echo "== serving smoke (replica + dynamic batcher + live weight refresh)"
# The inference tier's acceptance across real process/socket boundaries
# (docs/SERVING.md): one replica serves 64 concurrent requests through
# the dynamic batcher with at most len(buckets) predict compiles
# (profiler.record_dispatch pins it), exposes p50/p99/QPS, and a live
# dist_async push + version bump changes served predictions WITHOUT a
# replica restart.  Time-boxed: a batching or refresh regression
# typically presents as a hang; the in-process twins live in
# tests/test_serving.py.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 1 -s 1 \
    python tests/dist/dist_serving_smoke.py

echo "== fleet chaos smoke (kill one of three mid-storm + a blackhole)"
# ISSUE 17's fleet acceptance (docs/SERVING.md): a FleetClient over 3
# real replica processes survives one replica REALLY SIGKILLed
# mid-storm (MXNET_FI_KILL_PROCESS_AFTER) and a second gray-failed
# (MXNET_FI_BLACKHOLE_AFTER: accepts requests, never replies) with
# ZERO failed client requests out of a 64-thread predict storm; the
# routing counters prove follow-up traffic shifted entirely off both
# casualties, and tools/postmortem.py names the SIGKILLed corpse from
# bundle ABSENCE.  Self-launching (the script spawns its own replicas).
# Time-boxed: a retry/quarantine regression presents as a failed
# request or a hang on a swallowed reply.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tests/dist/dist_fleet_chaos.py

echo "== fleet canary rollback smoke (forced SLO regression)"
# The versioned-rollout acceptance (docs/SERVING.md): a 50/50 canary
# split against a replica whose replies are delayed 80 ms
# (MXNET_FI_DELAY_ACK_MS) must auto-roll back mid-stream on the p99
# SLO breach — canary drained, canary_rollback in the flight recorder,
# follow-up traffic 100% baseline — with zero failed requests (slow is
# not broken; the rollback is the point).  Self-launching.
JAX_PLATFORMS=cpu timeout -k 10 180 \
    python tests/dist/dist_fleet_canary.py

echo "== tracing smoke (spans on the wire + merged timeline + stats sweep)"
# ISSUE 12's cluster-observability gate (docs/OBSERVABILITY.md): a
# 2-worker/1-server launcher job with MXNET_TRACE=1 must (a) pass the
# in-process stats sweep — kv.server_stats per server and
# distributed.cluster_stats() returning every rank's counters — inside
# dist_tracing_smoke.py, and (b) leave per-process span journals that
# trace_merge --spans stitches into ONE chrome trace with spans from
# >= 3 processes and >= 1 cross-process flow arrow (a worker-side kv op
# linked to its server-side child span).  Time-boxed: a propagation
# regression presents as a missing span/flow, a flush regression as an
# empty journal.
rm -rf /tmp/_trace_smoke && mkdir -p /tmp/_trace_smoke
JAX_PLATFORMS=cpu MXNET_TRACE=1 MXNET_TRACE_DIR=/tmp/_trace_smoke \
    timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    python tests/dist/dist_tracing_smoke.py
python tools/trace_merge.py --spans /tmp/_trace_smoke \
    -o /tmp/_trace_merged.json
JAX_PLATFORMS=cpu python - <<'PY'
import json
m = json.load(open("/tmp/_trace_merged.json"))
md = m["metadata"]
pids = {e["pid"] for e in m["traceEvents"] if e.get("ph") == "X"}
assert len(pids) >= 3, f"expected spans from >= 3 processes, got {pids}"
assert md["cross_process_flows"] >= 1, md
print("tracing smoke OK: %d spans, %d processes, %d flows"
      % (md["spans"], len(pids), md["cross_process_flows"]))
PY

echo "== health smoke (injected barrier stall -> watchdog -> DEGRADED -> OK)"
# The ISSUE 13 acceptance's first half: a launcher run with an INJECTED
# barrier stall (faultinject.delay_barrier_release via
# MXNET_FI_STALL_BARRIER_MS — a deterministic wedge, no dead process)
# must trip the stall watchdog within its configured budget on every
# process (workers on kv.barrier, the server on its park), flip cluster
# health to DEGRADED on the server's universal ("stats",) reply and in
# distributed.cluster_health(), and RECOVER to OK through the
# hysteresis window once the stall clears — no restart, no manual
# reset.  The assertions live in the script, per rank.  Time-boxed: a
# watchdog regression presents as a failed assertion, a recovery
# regression as a stuck DEGRADED.
JAX_PLATFORMS=cpu timeout -k 10 240 \
    python tools/launch.py -n 2 -s 1 \
    --env MXNET_FI_STALL_BARRIER_MS=3000 \
    --env MXNET_HEALTH_BARRIER_STALL_S=0.4 \
    --env MXNET_HEALTH_INTERVAL_S=0.1 \
    --env MXNET_HEALTH_RECOVERY_S=1.0 \
    python tests/dist/dist_health_smoke.py

echo "== multichip dryrun (8 virtual devices)"
JAX_PLATFORMS=cpu python - <<'PY'
import cpu_pin
cpu_pin.pin_cpu(8)
import __graft_entry__ as ge
ge.dryrun_multichip(8)
print("dryrun_multichip(8) OK")
PY

echo "== CI green"
