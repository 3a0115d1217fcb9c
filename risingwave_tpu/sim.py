"""Deterministic simulation / chaos harness.

Counterpart of the reference's madsim deterministic cluster
(reference: src/tests/simulation/src/cluster.rs:129-247 — the whole
cluster in one process under a seeded scheduler, with ``--kill`` randomly
restarting nodes mid-workload; recovery tests
tests/integration_tests/recovery/). Scaled to this build's architecture:
the "cluster" is a durable Session; a *kill* abandons it without any
graceful shutdown and recovers a fresh Session from the same data dir
(crash recovery path), at epochs chosen by a seeded RNG.

Client semantics are honest: DML acknowledged only at FLUSH; statements
not yet flushed when a kill strikes are re-applied by the harness (client
retry), exactly how an at-least-once client driver behaves against the
reference. The end-state cross-check compares every MV against a control
session that never crashed — and, since ISSUE 9, every readable SINK's
delivered output (the surface the ConsistencyAuditor checks), so chaos
entries catch sink dupes/loss, not just MV divergence.

Two DETERMINISTIC modes ride on the network fault plane (rpc/faults.py):

* **named netsplit scenarios** (``run_netsplit``) — seeded
  ``ChaosSchedule``s over a live cluster: partition one exchange edge of
  a spanning 2-worker q5 graph for a window of epochs mid-stream, delay
  acks past the permit budget, duplicate+reorder exchange frames,
  duplicate a batch_task reply. Each run ends in a ConsistencyAuditor
  pass against a no-chaos control and returns its per-link injection
  trace; replaying the same seed reproduces the identical trace.
* **crash-point sweep** (``crash_point_sweep``) — iterate every
  registered failpoint site (common/failpoint.py KNOWN_SITES, including
  both 2PC checkpoint phases), kill the cluster the moment the site
  fires, recover, and audit — FoundationDB-style "die at every
  interesting instruction" coverage.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, List, Optional

from .common.compile_cache import export_compile_cache_env
from .frontend.session import Session
from .rpc.faults import CHAOS_ENV, ChaosRule, ChaosSchedule, install, plane


class CrashPoint(BaseException):
    """Raised by an armed failpoint to simulate process death AT that
    site: BaseException so no intermediate ``except Exception`` recovery
    layer can absorb it — the only handler is the sweep's kill path."""


class SimCluster:
    def __init__(self, data_dir: str, seed: int = 0, kill_rate: float = 0.3,
                 checkpoint_frequency: int = 2, workers: int = 0,
                 transient_fault_rate: float = 0.0,
                 broker=None, broker_restart_rate: float = 0.0,
                 chaos: Optional[ChaosSchedule] = None,
                 **session_kw):
        """``workers`` > 0 runs MV jobs on worker PROCESSES and arms
        per-component kills: the chaos step randomly SIGKILLs one worker
        (scoped heartbeat-TTL recovery) instead of always restarting the
        whole cluster — the madsim individual-node kill
        (reference: cluster.rs:498-510).

        ``transient_fault_rate`` > 0 arms SEEDED transient object-store
        faults for the whole workload (every durable-tier IO may fail and
        be retried — storage/object_store.py FaultInjectingObjectStore
        under the retry layer), proving the exactly-once machinery holds
        under flaky IO, not just clean kills. ``broker`` (a BrokerServer
        with a durable data_dir) + ``broker_restart_rate`` add broker
        restarts to the chaos menu: readers/sinks must survive via the
        reconnecting BrokerClient."""
        self.data_dir = data_dir
        self.rng = random.Random(seed)
        self.kill_rate = kill_rate
        self.session_kw = dict(session_kw,
                               checkpoint_frequency=checkpoint_frequency)
        if workers:
            self.session_kw["workers"] = workers
        if transient_fault_rate > 0.0 and \
                "fault_config" not in self.session_kw:
            from .common.config import FaultConfig
            self.session_kw["fault_config"] = FaultConfig(
                inject_object_store_transient_rate=transient_fault_rate,
                inject_object_store_seed=self.rng.randrange(1 << 30),
                # faults at rate p need attempts n with p**n ≈ 0:
                # 8 attempts at p=0.2 leaves ~3e-6 per op
                io_retry_attempts=8, io_retry_base_ms=1.0,
                io_retry_max_ms=20.0)
        self.broker = broker
        self.broker_restart_rate = broker_restart_rate
        self.broker_restarts = 0
        # network fault plane: install the schedule in THIS process and
        # export it so worker subprocesses (including recovery respawns)
        # adopt it at bring-up; injection traces persist under data_dir
        # so a killed process's trace survives for replay comparison
        self.chaos = chaos
        self._chaos_env_set = False
        if chaos is not None:
            os.environ[CHAOS_ENV] = chaos.to_json()
            self._chaos_env_set = True
            install(chaos, trace_path=os.path.join(
                data_dir, "chaos_trace_session.jsonl"))
        self.session = Session(data_dir=data_dir, **self.session_kw)
        self.kills = 0
        self.worker_kills = 0
        self.spanning_kills = 0
        self._unacked: List[str] = []     # DML since the last FLUSH

    # -- client API -----------------------------------------------------------

    def run_sql(self, sql: str) -> list:
        out = self.session.run_sql(sql)
        s = sql.lstrip().lower()
        if s.startswith("insert"):
            self._unacked.append(sql)
        elif s.startswith("flush"):
            self._unacked.clear()
        return out

    def flush(self) -> None:
        self.session.flush()
        self._unacked.clear()

    def tick(self) -> None:
        self.session.tick()

    def mv_rows(self, name: str) -> list:
        return self.session.mv_rows(name)

    # -- chaos ----------------------------------------------------------------

    def maybe_kill(self) -> bool:
        # broker restarts draw independently: a flaky broker AND a
        # crashing cluster may strike in the same step
        if (self.broker is not None and self.broker_restart_rate > 0
                and self.rng.random() < self.broker_restart_rate):
            self.restart_broker()
        if self.rng.random() >= self.kill_rate:
            return False
        if getattr(self.session, "workers", None) and \
                self.rng.random() < 0.5:
            # spanning fragment graphs get their own chaos entry: kill a
            # worker that hosts ONE fragment of a multi-worker graph
            # (scoped rebuild of that graph, every other job untouched)
            if getattr(self.session, "_spanning_specs", None) and \
                    self.rng.random() < 0.5:
                self.kill_spanning_worker()
            else:
                self.kill_worker()
        else:
            self.kill()
        return True

    def restart_broker(self) -> None:
        """Bounce the external broker on the SAME address (durable
        segments reload): in-flight client commands fail and must be
        absorbed by BrokerClient's reconnect-with-backoff."""
        from .connector.broker import BrokerServer
        old = self.broker
        host, port = old.host, old.port
        old.close()
        self.broker = BrokerServer(
            host=host, port=port, n_partitions=old.n_partitions,
            data_dir=old.data_dir).start()
        self.broker_restarts += 1

    def kill_worker(self) -> None:
        """SIGKILL one worker process (per-component failure): the
        session survives; the heartbeat TTL declares the worker's jobs
        dead and scoped recovery respawns it on subsequent ticks."""
        w = self.rng.choice(self.session.workers)
        w.kill9()
        self.worker_kills += 1
        for _ in range(12):               # TTL + respawn happen in-tick
            self.session.tick()
            if not w.dead:
                return
        raise AssertionError("killed worker was not recovered")

    def kill_spanning_worker(self) -> None:
        """SIGKILL one worker hosting a FRAGMENT of a spanning graph:
        surviving peers report PEER_LOST on their exchange edges, the
        TTL declares the job dead, and scoped recovery must rebuild ONLY
        the affected fragment graph (respawned worker + surviving
        fragments reloaded at the last commit) and converge — asserted
        here, cross-checked against the control session by the caller."""
        specs = self.session._spanning_specs
        name = self.rng.choice(sorted(specs))
        w = self.rng.choice(specs[name]["workers"])
        w.kill9()
        self.worker_kills += 1
        self.spanning_kills += 1
        for _ in range(16):               # TTL + scoped rebuild in-tick
            self.session.tick()
            job = self.session.jobs.get(name)
            if not w.dead and job is not None and job._failure is None:
                return
        raise AssertionError(
            f"spanning job {name!r} did not converge after a "
            "participant kill")

    def kill(self) -> None:
        """Abandon the session with no shutdown (uncommitted state and
        unacked DML are lost), then recover + re-apply unacked DML."""
        self.kills += 1
        # crash semantics: no job shutdown, no flush — but kill the old
        # worker PROCESSES (their parent is gone, like a machine reboot)
        # and close the abandoned private event loop so kills don't leak
        old = self.session
        for w in getattr(old, "workers", []) or []:
            try:
                w.kill9()
            except Exception:   # noqa: BLE001
                pass
        try:
            old.loop.close()
        except Exception:   # noqa: BLE001
            pass
        self.session = Session(data_dir=self.data_dir, **self.session_kw)
        for sql in self._unacked:
            self.session.run_sql(sql)

    # -- verification ---------------------------------------------------------

    def verify_against(self, control: Session,
                       mv_names: Optional[List[str]] = None) -> None:
        """Final-state cross-check after both sides flushed: every MV
        bit-equal AND every readable sink's DELIVERED output equal as a
        multiset (the surface the ConsistencyAuditor checks — a chaos
        run that re-delivered or lost sink rows fails here even when
        the MVs converged)."""
        from .common.audit import fold_changelog, sink_delivered_rows
        self.flush()
        control.flush()
        names = mv_names or sorted(self.session.catalog.mvs)
        for name in names:
            got = sorted(self.mv_rows(name))
            want = sorted(control.mv_rows(name))
            assert got == want, (
                f"MV {name!r} diverged after {self.kills} kills:\n"
                f"  chaos:   {got[:10]}\n  control: {want[:10]}")
        for name in sorted(set(self.session.catalog.sinks)
                           & set(control.catalog.sinks)):
            got_s = sink_delivered_rows(self.session, name)
            want_s = sink_delivered_rows(control, name)
            if got_s is None or want_s is None:
                continue               # backend not readable: skip
            assert fold_changelog(got_s) == fold_changelog(want_s), (
                f"sink {name!r} delivery diverged after {self.kills} "
                f"kills: {len(got_s)} rows delivered vs {len(want_s)} "
                "expected (dupes or loss in the folded changelog)")

    def close(self) -> None:
        """Tear down the cluster and clear the exported chaos schedule
        (so later sessions in this process spawn clean workers)."""
        if self._chaos_env_set:
            os.environ.pop(CHAOS_ENV, None)
            self._chaos_env_set = False
            install(None)
        try:
            self.session.close()
        except Exception:   # noqa: BLE001 - best-effort teardown
            pass


# ---------------------------------------------------------------------------
# Named netsplit scenarios (deterministic network-fault runs)
# ---------------------------------------------------------------------------

_BID_DDL = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, "
            "price BIGINT, channel VARCHAR, url VARCHAR, "
            "date_time TIMESTAMP, extra VARCHAR) "
            "WITH (connector = 'nexmark', nexmark_table = 'bid')")

_Q5 = """CREATE MATERIALIZED VIEW q5 AS
    SELECT AuctionBids.auction, AuctionBids.num FROM (
        SELECT bid.auction, count(*) AS num, window_start AS starttime
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY window_start, bid.auction
    ) AS AuctionBids
    JOIN (
        SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
        FROM (
            SELECT count(*) AS num, window_start AS starttime_c
            FROM HOP(bid, date_time, INTERVAL '2' SECOND,
                     INTERVAL '10' SECOND)
            GROUP BY bid.auction, window_start
        ) AS CountBids
        GROUP BY CountBids.starttime_c
    ) AS MaxBids
    ON AuctionBids.starttime = MaxBids.starttime_c
       AND AuctionBids.num = MaxBids.maxn"""

_AGG = ("CREATE MATERIALIZED VIEW q AS SELECT auction, count(*) AS n, "
        "max(price) AS mx FROM bid GROUP BY auction")

#: named scenarios: mv SQL, which schedule to arm, and whether the
#: injection is expected to force a scoped recovery (partition) or be
#: absorbed transparently by the hardening (dedup/reorder/keepalive)
NETSPLIT_SCENARIOS: Dict[str, dict] = {
    # partition ONE exchange edge of the spanning 2-worker q5 graph for
    # 3 epochs mid-stream: barrier collection on the starved consumer
    # trips the epoch deadline, scoped recovery rebuilds the graph from
    # per-worker durable state, sources replay, and the MV converges
    # bit-exact with a no-chaos control (the ISSUE 9 acceptance run)
    "q5_exchange_partition": {
        "sql": _Q5, "mv": "q5", "expect_recovery": True,
        "rules": lambda e0: [ChaosRule(
            kind="partition", link="w0->w1", types=["exg_data"],
            epochs=[e0, e0 + 3])],
    },
    # duplicate + reorder exchange frames on the w0<->w1 edges: the
    # per-channel seq layer dedups and re-sequences, so the run needs NO
    # recovery and stays bit-exact (exactly-once from at-least-once)
    "exchange_dup_reorder": {
        "sql": _AGG, "mv": "q", "expect_recovery": False,
        "rules": lambda e0: [
            ChaosRule(kind="duplicate", link="w0<->w1",
                      types=["exg_data"], prob=0.3),
            ChaosRule(kind="delay", link="w0<->w1",
                      types=["exg_data:chunk"], prob=0.25,
                      delay_frames=2),
        ],
    },
    # delay consumption acks on the exchange edges: producers stall on
    # permits (permits_waited grows) but nothing is lost — backpressure
    # is the correct, convergent behavior
    "ack_delay": {
        "sql": _AGG, "mv": "q", "expect_recovery": False,
        "rules": lambda e0: [ChaosRule(
            kind="delay", link="w0<->w1", types=["exg_ack"],
            delay_ms=30.0)],
    },
    # duplicate every worker→session reply frame: request/reply rid
    # dedup keeps batch_task / scan results exactly-once at the caller.
    # The query runs the serving plane's TWO-PHASE path over the
    # sharded-root spanning MV, so real batch_task replies (one per
    # slice-holding worker) cross the faulty link and get duplicated.
    "dup_batch_reply": {
        "sql": _AGG, "mv": "q", "expect_recovery": False,
        "query": "SELECT auction, count(*) AS c FROM q GROUP BY auction",
        "rules": lambda e0: [ChaosRule(
            kind="duplicate", link="w*->s", types=["reply"])],
    },
}


def netsplit_schedule(name: str, seed: int,
                      base_ticks: int = 2) -> ChaosSchedule:
    """Build the seeded schedule for one named scenario. The fault
    window is expressed in ABSOLUTE epochs: the setup below (DDL, then
    ``base_ticks`` lockstep ticks, then FLUSH) lands the cluster at
    epoch ``base_ticks + 2``, so the window opens on the next epoch —
    mid-stream, after a committed checkpoint cut."""
    spec = NETSPLIT_SCENARIOS[name]
    e0 = base_ticks + 3
    return ChaosSchedule(seed, spec["rules"](e0), name=name)


def _collect_trace(data_dir: str) -> Dict[str, list]:
    """Collect every persisted injection trace under ``data_dir``
    (chaos_trace.jsonl per worker incarnation, chaos_trace_session.jsonl
    for the session process), grouped per stream. Each plane install
    wrote an incarnation marker; events carry their incarnation index so
    two incarnations of the same stream (per-stream seqs restart at 0
    after a respawn) never collapse into one event. Per-stream
    per-incarnation event lists are the deterministic replay unit."""
    events: List[tuple] = []
    for root, _dirs, files in os.walk(data_dir):
        for f in sorted(files):
            if not (f.startswith("chaos_trace") and f.endswith(".jsonl")):
                continue
            inc = -1
            with open(os.path.join(root, f), encoding="utf-8") as fh:
                for line in fh:
                    if not line.strip():
                        continue
                    ev = json.loads(line)
                    if ev.get("marker") == "install":
                        inc += 1
                        continue
                    events.append((ev["link"], max(inc, 0), ev["seq"],
                                   ev["kind"], ev["type"], ev["rule"]))
    if not events:
        # no persisted files (plane installed without a trace_path):
        # fall back to the in-memory trace, one incarnation
        events = [(ev["link"], 0, ev["seq"], ev["kind"], ev["type"],
                   ev["rule"]) for ev in plane().trace]
    by_link: Dict[str, set] = {}
    for link, inc, seq, kind, ftype, rule in events:
        by_link.setdefault(link, set()).add((inc, seq, kind, ftype,
                                             rule))
    return {k: sorted(v) for k, v in by_link.items()}


def run_netsplit(name: str, seed: int = 7, data_dir: Optional[str] = None,
                 base_ticks: int = 2, post_ticks: int = 2,
                 chunk_capacity: int = 64,
                 session_kw: Optional[dict] = None) -> dict:
    """Run one named netsplit scenario end to end and machine-check the
    result: build a 2-worker cluster with the seeded schedule installed,
    run the scenario's MV as a spanning graph, let the injection strike
    (riding out a scoped recovery when the scenario forces one), then
    audit against a no-chaos single-process control. Returns a report
    with the per-link injection trace — re-running the same (name, seed)
    reproduces it identically."""
    import tempfile

    from .common.audit import ConsistencyAuditor
    from .common.config import FaultConfig
    from .frontend.build import BuildConfig

    spec = NETSPLIT_SCENARIOS[name]
    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_netsplit_")
    schedule = netsplit_schedule(name, seed, base_ticks)
    # short deadlines: a partitioned edge must trip the epoch deadline
    # in seconds, not the production 300s. NOT too short though: the
    # first data epoch of a fresh worker process pays XLA compilation,
    # and a deadline under that cost reads as a dead worker and spins
    # recovery forever (found by this very harness) — the shared
    # compilation cache below keeps RESPAWNED workers fast
    export_compile_cache_env()
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    # keepalive probing stays OFF here: detection rides the epoch
    # deadline (the probe's own regression test sets up a controlled
    # idle link instead — under q5's compute-bound epochs an aggressive
    # prober would race the busy event loop)
    fc = FaultConfig(worker_epoch_timeout_s=15.0,
                     worker_request_timeout_s=60.0,
                     exchange_keepalive_s=0.0)
    sim = SimCluster(data_dir, seed=seed, kill_rate=0.0, workers=2,
                     chaos=schedule, source_chunk_capacity=chunk_capacity,
                     checkpoint_frequency=2, fault_config=fc,
                     config=BuildConfig(fragment_parallelism=2),
                     **(session_kw or {}))
    control = Session(seed=42, source_chunk_capacity=chunk_capacity,
                      checkpoint_frequency=2)
    mv = spec["mv"]
    try:
        for sess in (sim.session, control):
            sess.run_sql(_BID_DDL)
            sess.run_sql(spec["sql"])
        assert mv in sim.session._spanning_specs, \
            f"{mv} did not deploy as a 2-worker spanning graph"
        for _ in range(base_ticks):
            sim.tick()
            control.tick()
        sim.flush()                    # committed cut before the window
        control.flush()
        recovered = False
        if spec["expect_recovery"]:
            # the window opens on the next epoch: tick the chaos side
            # alone until the starved graph died AND scoped recovery
            # rebuilt it (dead-window ticks feed the job nothing, and
            # the wedged epoch's uncommitted generate replays from the
            # committed offsets — so the control is NOT ticked here)
            for _ in range(40):
                sim.tick()
                s = sim.session
                job = s.jobs.get(mv)
                healthy = (job is not None and job._failure is None
                           and mv not in s._dead_jobs
                           and not any(w.dead for w in s.workers))
                if recovered and healthy:
                    break
                if not healthy:
                    recovered = True   # strike observed; await rebuild
            else:
                raise AssertionError(
                    f"netsplit {name!r} never recovered")
            assert recovered, f"netsplit {name!r} never struck"
        for _ in range(post_ticks):
            sim.tick()
            control.tick()
        # read MVs through the chaos side BEFORE auditing so a remote
        # scan path exercises the (possibly still chaotic) reply links
        _ = sim.mv_rows(mv)
        query_ok = None
        if spec.get("query"):
            # batch query through the chaos side's serving plane (two-
            # phase batch_task frames over the faulty links) must equal
            # the control's answer EXACTLY ONCE — a duplicated reply
            # that slipped rid-dedup would double rows here
            got_q = sorted(sim.session.run_sql(spec["query"]))
            want_q = sorted(control.run_sql(spec["query"]))
            assert got_q == want_q, (
                f"query diverged under chaos: {got_q[:5]} vs "
                f"{want_q[:5]}")
            query_ok = True
        sim.verify_against(control, [mv])
        report = ConsistencyAuditor(sim.session).audit(control=control)
        report.assert_ok()
        metrics = sim.session.metrics()
        out = {
            "scenario": name, "seed": seed,
            "schedule": schedule.to_json(),
            "recovered": recovered,
            "rows": len(sim.mv_rows(mv)),
            "query_ok": query_ok,
            "chaos": metrics["chaos"],
            "audit": {k: v.get("ok") for k, v in report.checks.items()},
        }
    finally:
        sim.close()
        control.close()
    out["trace"] = _collect_trace(data_dir)
    return out


# ---------------------------------------------------------------------------
# Traffic-spike scenario (elastic scaling plane, docs/scaling.md)
# ---------------------------------------------------------------------------

def run_traffic_spike(seed: int = 7, data_dir: Optional[str] = None,
                      workers: int = 4, warmup_ticks: int = 2,
                      spike_rate: int = 8, settle_ticks: int = 6,
                      chunk_capacity: int = 32) -> dict:
    """The scaling plane's acceptance scenario: a spanning grouped-agg
    job runs at parallelism 2 on a ``workers``-process cluster with the
    autoscaler armed; a seeded traffic spike (source rate jumps to
    ``spike_rate`` chunks/tick over a tiny exchange permit budget)
    drives permits_waited up, the autoscaler scales the job out 2→4 via
    LIVE vnode migration (only the changed ranges move — asserted from
    the migration metrics), and when the load subsides the policy's
    cooldown + scale-in laziness keep it from flapping. The end state is
    cross-checked bit-exact against a no-spike-plumbing control and the
    ConsistencyAuditor must come back green."""
    import tempfile

    from .common.audit import ConsistencyAuditor
    from .common.config import AutoscalerConfig, FaultConfig
    from .frontend.build import BuildConfig

    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_spike_")
    export_compile_cache_env()
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    acfg = AutoscalerConfig(
        enabled=True, high_permits_waited=1, hysteresis=2, cooldown=8,
        scale_in_after=64, max_parallelism=min(4, workers))
    fc = FaultConfig(worker_epoch_timeout_s=60.0,
                     worker_request_timeout_s=120.0,
                     exchange_keepalive_s=0.0)
    sim = SimCluster(data_dir, seed=seed, kill_rate=0.0, workers=workers,
                     source_chunk_capacity=chunk_capacity,
                     checkpoint_frequency=2, fault_config=fc,
                     config=BuildConfig(fragment_parallelism=2,
                                        exchange_permits=2),
                     autoscaler_config=acfg)
    control = Session(seed=42, source_chunk_capacity=chunk_capacity,
                      checkpoint_frequency=2)
    mv = "q"
    try:
        for sess in (sim.session, control):
            sess.run_sql(_BID_DDL)
            sess.run_sql(_AGG)
        assert mv in sim.session._spanning_specs, \
            f"{mv} did not deploy as a spanning graph"
        spec = sim.session._spanning_specs[mv]
        assert max(len(a) for a in
                   spec["placement"].actors.values()) == 2

        def par() -> int:
            return max(len(a) for a in spec["placement"].actors.values())

        for _ in range(warmup_ticks):
            sim.tick()
            control.tick()
        # SPIKE: raise the source rate on chaos side AND control — the
        # control consumes the same rows without the scaling plumbing
        sim.session.set_source_rate(spike_rate)
        control.chunks_per_tick = spike_rate
        spike_ticks = 0
        for _ in range(24):
            sim.tick()
            control.tick()
            spike_ticks += 1
            if par() == acfg.max_parallelism:
                break
        assert par() == acfg.max_parallelism, (
            f"autoscaler never scaled out (parallelism {par()}, "
            f"status {sim.session.autoscaler.status()})")
        decisions_at_peak = len(sim.session.autoscaler.decisions)
        last = sim.session._rescale_stats["last"]
        moved = last["moved_vnodes"]
        from .common.hashing import VNODE_COUNT
        # only the CHANGED ranges moved: one sharded fragment halves its
        # per-actor ranges, so exactly half the ring changes owner
        assert moved == VNODE_COUNT // 2, (
            f"expected {VNODE_COUNT // 2} moved vnodes, got {moved}: "
            f"{last['moved_ranges']}")
        # SUBSIDE: load returns to 1 chunk/tick; cooldown + scale-in
        # laziness must keep the topology steady (no flapping)
        sim.session.set_source_rate(1)
        control.chunks_per_tick = 1
        for _ in range(settle_ticks):
            sim.tick()
            control.tick()
        assert par() == acfg.max_parallelism, "autoscaler flapped"
        assert len(sim.session.autoscaler.decisions) == \
            decisions_at_peak, "autoscaler flapped after load subsided"
        sim.verify_against(control, [mv])
        report = ConsistencyAuditor(sim.session).audit(control=control)
        report.assert_ok()
        metrics = sim.session.metrics()
        return {
            "scenario": "traffic_spike", "seed": seed,
            "parallelism": par(), "moved_vnodes": moved,
            "pause_ms": last["pause_ms"],
            "spike_ticks": spike_ticks,
            "decisions": list(sim.session.autoscaler.decisions),
            "rows": len(sim.mv_rows(mv)),
            "audit": {k: v.get("ok") for k, v in report.checks.items()},
        }
    finally:
        sim.close()
        control.close()


# ---------------------------------------------------------------------------
# Crash-point sweep (die at every registered failpoint, audit after each)
# ---------------------------------------------------------------------------

def _sweep_tax(v):
    """Module-level so the UDF plane ships it to the server BY REFERENCE
    (udf/registry.py) — the sweep's UDF workload step."""
    return v * 2 + 1


def _chaos_tax(v):
    """Module-level → ships to the UDF server by reference (the chaos
    scenario's and the soak's workload UDF)."""
    return v * 3 + 7


def _ensure_udf(name: str, fn) -> None:
    """Register a harness UDF once per process (INT64 → INT64)."""
    from .expr.expr import _REGISTRY
    if name not in _REGISTRY:
        from .common.types import INT64
        from .expr.udf import register_udf
        register_udf(name, fn, [INT64], INT64)


def _sweep_workload_stmts(sink_path: str) -> List[tuple]:
    """(sql, kind) steps: DDL first, then interleaved DML/FLUSH with a
    mid-stream CREATE (so meta-store txns fire mid-workload too) and a
    UDF-evaluating SELECT (so the udf.* client failpoint sites fire
    mid-workload — ISSUE 15)."""
    steps: List[tuple] = [
        ("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)", "ddl:t"),
        ("CREATE MATERIALIZED VIEW m AS SELECT sum(v) AS n FROM t",
         "ddl:m"),
        (f"CREATE SINK snk FROM m WITH (connector = 'file', "
         f"path = '{sink_path}')", "ddl:snk"),
    ]
    for i in range(1, 9):
        steps.append((f"INSERT INTO t VALUES ({i}, {10 * i})", "dml"))
        if i % 2 == 0:
            steps.append(("FLUSH", "flush"))
        if i == 4:
            steps.append(
                ("CREATE MATERIALIZED VIEW m2 AS "
                 "SELECT count(*) AS c FROM t", "ddl:m2"))
        if i == 6:
            steps.append(("SELECT k, sweep_tax(v) FROM t", "query"))
    steps.append(("FLUSH", "flush"))
    return steps


def _exists(session: Session, kind: str) -> bool:
    name = kind.split(":", 1)[1]
    cat = session.catalog
    return (name in cat.tables or name in cat.mvs or name in cat.sinks)


def crash_point_sweep(base_dir: str, sites: Optional[List[str]] = None,
                      seed: int = 0,
                      audit: bool = True) -> Dict[str, dict]:
    """FoundationDB-style sweep: for EVERY registered failpoint site run
    the same durable workload, crash the cluster the moment the site
    fires (``CrashPoint`` is a BaseException no recovery layer can
    absorb), recover, finish the workload, and let the
    ``ConsistencyAuditor`` assert exactly-once sinks / MV parity /
    monotone barriers / pin leak-freedom against an unharmed control.
    Sites the workload never executes are reported ``not_hit`` honestly.
    Worker-resident sites (the 2PC prepare/commit phases of a SPANNING
    graph) are exercised by ``crash_point_sweep_spanning``."""
    from .common.audit import ConsistencyAuditor
    from .common.failpoint import arm, disarm, registered_sites

    _ensure_udf("sweep_tax", _sweep_tax)
    sites = sites if sites is not None else registered_sites()
    results: Dict[str, dict] = {}
    for i, site in enumerate(sites):
        tier = ("hummock" if site.startswith(("hummock.", "compactor."))
                else "segment")
        d = os.path.join(base_dir, f"site_{i:02d}")
        sink_chaos = os.path.join(d, "sink_chaos.jsonl")
        sink_ctl = os.path.join(d, "sink_ctl.jsonl")
        steps = _sweep_workload_stmts(sink_chaos)
        control = Session(data_dir=os.path.join(d, "ctl"), seed=seed,
                          checkpoint_frequency=2, state_store=tier)
        sim = SimCluster(os.path.join(d, "chaos"), seed=seed,
                         kill_rate=0.0, checkpoint_frequency=2,
                         state_store=tier)
        hit = [False]

        def _trip(_site=site, _hit=hit):
            _hit[0] = True
            raise CrashPoint(_site)

        try:
            # control first, UNARMED: the failpoint registry is
            # process-global, so arming before the control ran would
            # crash the control too
            for sql, _kind in steps:
                control.run_sql(sql.replace(sink_chaos, sink_ctl))
            control.flush()
            if site.startswith("udf."):
                # the UDF server is process-global and the control's
                # SELECT just spawned it — tear it down so the ARMED
                # run exercises udf.spawn (and the others) itself
                from .udf.client import udf_plane
                udf_plane().shutdown_server()
            for sql, kind in steps:
                if kind == "ddl:snk":
                    # arm AFTER setup DDL: the sweep's subject is the
                    # running cluster, not bootstrap
                    arm(site, _trip, once=True)
                try:
                    sim.run_sql(sql)
                except BaseException:
                    # CrashPoint propagates directly from IO-path sites;
                    # a site inside a stream actor surfaces as the job's
                    # failure (RuntimeError) instead — either way, if
                    # the armed site JUST fired this IS the simulated
                    # crash. Errors before the site fired, or after its
                    # one crash was already taken, are real bugs.
                    if not hit[0] or _ARMED_SWEEP_KILLED.get(site):
                        raise
                    _ARMED_SWEEP_KILLED[site] = True
                    sim.kill()         # die AT the site; recover; retry
                    if kind.startswith("ddl") \
                            and not _exists(sim.session, kind):
                        sim.run_sql(sql)   # client retries a lost DDL
                if hit[0] and not _ARMED_SWEEP_KILLED.get(site):
                    # the site fired on a BACKGROUND thread (inline
                    # compaction): the thread died, the main path did
                    # not — still crash the cluster at this moment
                    _ARMED_SWEEP_KILLED[site] = True
                    sim.kill()
            try:
                sim.flush()
            except BaseException:       # armed-once site fired at the
                if not hit[0] or _ARMED_SWEEP_KILLED.get(site):
                    raise               # closing flush: die there too,
                _ARMED_SWEEP_KILLED[site] = True
                sim.kill()              # recover, and flush clean
                sim.flush()
            status: dict = {"hit": hit[0], "kills": sim.kills}
            sim.verify_against(control)
            if audit:
                report = ConsistencyAuditor(sim.session).audit(
                    control=control)
                report.assert_ok()
                status["audit"] = "ok"
            results[site] = status
        finally:
            disarm(site)
            _ARMED_SWEEP_KILLED.pop(site, None)
            sim.close()
            control.close()
    return results


_ARMED_SWEEP_KILLED: Dict[str, bool] = {}


def crash_point_sweep_spanning(base_dir: str, seed: int = 3,
                               sites: Optional[List[str]] = None
                               ) -> Dict[str, dict]:
    """The 2PC checkpoint phases fire inside WORKER processes of a
    spanning graph. For each phase site, arm a REAL process exit at the
    site via the RWTPU_FAILPOINTS env (the worker dies with ``os._exit``
    the first time it reaches the site — a marker file keeps the
    respawned worker from dying forever), then prove the heartbeat-TTL
    scoped recovery converges and the auditor passes against a no-chaos
    control."""
    from .common.audit import ConsistencyAuditor
    from .common.config import FaultConfig
    from .frontend.build import BuildConfig

    # checkpoint.prepare = phase 1 (durable staging before the ack);
    # checkpoint.settle = phase 2 (the commit frame promoting the
    # staged epoch) — settle, not append, is the prepared-epoch path
    sites = sites or ["checkpoint.prepare", "checkpoint.settle"]
    results: Dict[str, dict] = {}
    for i, site in enumerate(sites):
        d = os.path.join(base_dir, f"span_{i:02d}")
        os.makedirs(d, exist_ok=True)
        marker = os.path.join(d, "died_once.marker")
        # ONE deterministic victim (worker 1): phase-2 commit frames
        # broadcast to every participant, and an unscoped exit would
        # race over how many workers die
        os.environ["RWTPU_FAILPOINTS"] = json.dumps(
            {site: {"action": "exit", "once_marker": marker,
                    "worker": 1}})
        # shared compile cache + generous deadline: a respawned worker's
        # first epoch pays XLA compilation (see run_netsplit)
        export_compile_cache_env()
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
        fc = FaultConfig(worker_epoch_timeout_s=15.0,
                         worker_request_timeout_s=60.0,
                         exchange_keepalive_s=0.0)
        # checkpoint ONLY at the explicit flush below, so the armed 2PC
        # site fires at a known point in the lockstep schedule (an
        # early-tick checkpoint would kill the worker mid-warmup and
        # desynchronize the control's generate accounting)
        sim = SimCluster(os.path.join(d, "chaos"), seed=seed,
                         kill_rate=0.0, workers=2,
                         source_chunk_capacity=64,
                         checkpoint_frequency=1000, fault_config=fc,
                         config=BuildConfig(fragment_parallelism=2))
        control = Session(seed=42, source_chunk_capacity=64,
                          checkpoint_frequency=1000)
        try:
            for sess in (sim.session, control):
                sess.run_sql(_BID_DDL)
                sess.run_sql(_AGG)
            assert "q" in sim.session._spanning_specs
            for _ in range(2):
                sim.tick()
            # the flush's checkpoint reaches the armed site in worker 1:
            # it EXITS there; the TTL + scoped recovery rebuild the
            # graph from the DECIDED cut. The two phases differ — that
            # is the contract under test:
            #   prepare-death: the victim never acked, so the epoch was
            #     never decided; every participant's prepared state is
            #     DISCARDED and the pre-flush ticks replay from zero
            #     (nothing earlier committed in this schedule);
            #   commit-death: every participant prepared + acked, so
            #     the epoch was decided; the victim's prepared state
            #     ROLLS FORWARD at recovery and the pre-flush ticks
            #     survive the crash.
            sim.flush()
            died = os.path.exists(marker)
            for _ in range(40):
                job = sim.session.jobs.get("q")
                if not any(w.dead for w in sim.session.workers) \
                        and job is not None and job._failure is None \
                        and "q" not in sim.session._dead_jobs \
                        and died:
                    break
                sim.tick()
                died = died or os.path.exists(marker)
            assert died, f"no worker reached site {site!r}"
            for _ in range(2):
                sim.tick()
            # effective generate ticks the chaos side's MV reflects:
            # post-recovery ticks, plus the rolled-forward pre-flush
            # ticks iff the decided epoch survived the crash
            pre_survived = 2 if site == "checkpoint.settle" else 0
            for _ in range(pre_survived + 2):
                control.tick()
            sim.verify_against(control, ["q"])
            report = ConsistencyAuditor(sim.session).audit(
                control=control)
            report.assert_ok()
            results[site] = {"hit": True, "audit": "ok",
                             "worker_kills": 1,
                             "rolled_forward": bool(pre_survived)}
        finally:
            os.environ.pop("RWTPU_FAILPOINTS", None)
            sim.close()
            control.close()
    return results


# ---------------------------------------------------------------------------
# UDF-plane chaos + soak (ISSUE 15 — the udf link joins the fault estate)
# ---------------------------------------------------------------------------

def udf_chaos_schedule(seed: int) -> ChaosSchedule:
    """Seeded faults on the UDF link: dropped call frames (the client's
    deadline trips → kill + seeded respawn + batch replay), delayed
    calls, and duplicated replies (the (gen, rid) fence drops the
    extras). Registration frames are deliberately NOT dropped (types
    filter) so a respawn's replay always lands — the drop rule models a
    flaky data path, the respawn protocol is what absorbs it. The drop
    rule is COUNT-capped below the retry budget (the same discipline
    the netsplit scenarios apply with bounded windows): per-seq seeded
    draws can otherwise align with the retry cadence (register/retry
    alternate seqs) and starve ANY bounded retry ladder — a statement
    about the schedule, not the plane."""
    return ChaosSchedule(seed, [
        ChaosRule(kind="drop", link="s->udf", types=["udf_call"],
                  prob=0.3, count=3),
        ChaosRule(kind="delay", link="s->udf", types=["udf_call"],
                  prob=0.3, delay_ms=5.0),
        ChaosRule(kind="duplicate", link="udf->s", prob=0.25),
    ], name="udf_link_chaos")


_UDF_T_DDL = "CREATE TABLE ut (k BIGINT PRIMARY KEY, v BIGINT)"
_UDF_MV = ("CREATE MATERIALIZED VIEW mu AS "
           "SELECT k, chaos_tax(v) AS tv FROM ut")
_COSCHED_MV = ("CREATE MATERIALIZED VIEW cq AS "
               "SELECT auction, count(*) AS n FROM bid GROUP BY auction")


def run_udf_chaos(seed: int = 11, data_dir: Optional[str] = None,
                  ticks: int = 6, kill_at: int = 3,
                  pipeline_depth: int = 1,
                  coschedule: bool = False) -> dict:
    """The UDF link's netsplit-style scenario: run a UDF-projecting MV
    (plus, optionally, a co-scheduled fused MV under the pipelined tick
    plane — ``pipeline_depth=2`` + ``coschedule=True`` is the ISSUE 15
    acceptance composition) under a seeded udf-link ChaosSchedule, with
    the server SIGKILLed mid-run, then audit bit-exact against a
    no-chaos control and return the per-link injection trace — the same
    (seed, workload) reproduces it identically.

    Unlike the exchange netsplits, chaos and control run SEQUENTIALLY:
    the UDF plane is process-global, so a lockstep control would share
    the faulty link."""
    import tempfile

    from .common.audit import ConsistencyAuditor
    from .common.config import UdfConfig
    from .frontend.build import BuildConfig
    from .udf.client import udf_plane

    _ensure_udf("chaos_tax", _chaos_tax)
    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_udfchaos_")
    plane_cfg = UdfConfig(call_timeout_s=2.0, max_retries=4,
                          spawn_timeout_s=30.0)
    udf_plane().configure(plane_cfg, trace_dir=data_dir)
    udf_plane().shutdown_server()     # fresh incarnation under chaos
    base_stats = dict(udf_plane().snapshot())
    session_kw: dict = {"pipeline_depth": pipeline_depth}
    if coschedule:
        session_kw["config"] = BuildConfig(coschedule=True)

    def workload(run_sql, tick, kill=None):
        run_sql(_UDF_T_DDL)
        if coschedule:
            run_sql(_BID_DDL)
            run_sql(_COSCHED_MV)
        run_sql(_UDF_MV)
        for i in range(ticks):
            run_sql(f"INSERT INTO ut VALUES ({i + 1}, {100 * (i + 1)})")
            if kill is not None and i == kill_at:
                kill()          # SIGKILL the server; next batch respawns
            tick()
        run_sql("SELECT k, chaos_tax(v) FROM ut")

    schedule = udf_chaos_schedule(seed)
    sim = SimCluster(data_dir, seed=seed, kill_rate=0.0,
                     chaos=schedule, checkpoint_frequency=2,
                     **session_kw)
    try:
        workload(sim.run_sql, sim.tick, kill=udf_plane().kill_server)
        sim.flush()
        trace = {k: v for k, v in _collect_trace(data_dir).items()
                 if k.split("#")[0] in ("s->udf", "udf->s")}
        injections = dict(plane().injections)
        cosched_groups = len(
            sim.session.metrics().get("coschedule") or {})
        # the chaos phase's plane deltas are final HERE — the control
        # phase below must not fold into them
        stats = udf_plane().snapshot()
        # chaos OFF for the control phase: clear the client plane AND
        # retire the chaos-era server — it installed the schedule from
        # RWTPU_CHAOS at spawn, so keeping it would duplicate the
        # control's replies (the control would not actually be
        # chaos-free)
        install(None)
        os.environ.pop(CHAOS_ENV, None)
        sim._chaos_env_set = False
        udf_plane().shutdown_server()
        control = Session(checkpoint_frequency=2, **session_kw)
        try:
            workload(control.run_sql, control.tick)
            control.flush()
            mvs = ["mu"] + (["cq"] if coschedule else [])
            sim.verify_against(control, mvs)
            report = ConsistencyAuditor(sim.session).audit(
                control=control)
            report.assert_ok()
            return {
                "scenario": "udf_link_chaos", "seed": seed,
                "pipeline_depth": pipeline_depth,
                "coschedule": coschedule,
                "cosched_groups": cosched_groups,
                "respawns": stats["respawns"] - base_stats["respawns"],
                "spawns": stats["spawns"] - base_stats["spawns"],
                "timeouts": stats["timeouts"] - base_stats["timeouts"],
                "stale_replies_dropped":
                    stats["stale_replies_dropped"]
                    - base_stats["stale_replies_dropped"],
                "injections": injections,
                "trace": trace,
                "rows": len(sim.mv_rows("mu")),
                "audit": {k: v.get("ok")
                          for k, v in report.checks.items()},
            }
        finally:
            control.close()
    finally:
        sim.close()


def meta_chaos_schedule(seed: int) -> ChaosSchedule:
    """Seeded delays on the session→meta RPC link (meta/client.py
    META_LINK). Delay-only BY DESIGN: the meta protocol is sequential
    request/reply on one socket with no per-request ids, so the
    absorb-or-degrade contract under latency is "ticks slow down,
    nothing diverges" — frame drops/dups model a failed meta process,
    which is the kill -9 restart test's job
    (tests/test_meta_control_plane.py), not a frame-level fault."""
    from .meta.client import META_LINK
    return ChaosSchedule(seed, [
        ChaosRule(kind="delay", link=META_LINK, prob=0.4, delay_ms=3.0),
        # EVERY lease heartbeat delayed too (the lease.* frames ride
        # their own `meta#clease` chaos stream — meta/client.py): a slow
        # meta link slows renewals down but must NEVER expire a live
        # writer's lease, or latency alone would trigger failovers —
        # run_meta_chaos asserts the term never moved
        ChaosRule(kind="delay", link=META_LINK, types=["lease.renew"],
                  prob=1.0, delay_ms=2.0),
    ], name="meta_link_delay")


def run_meta_chaos(seed: int = 13, data_dir: Optional[str] = None,
                   ticks: int = 5) -> dict:
    """Meta-link latency scenario (docs/control-plane.md): a writer
    session attached to a STANDALONE MetaServer runs DDL + DML + ticks
    while every meta RPC frame is seeded-delayed; a serving session then
    attaches over the same slow link and must converge on the writer's
    catalog and data. Audited bit-exact against an in-process control
    (which never touches the faulty link). Returns the per-link
    injection trace — the same seed reproduces it identically."""
    import tempfile

    from .common.audit import ConsistencyAuditor
    from .meta.client import META_LINK
    from .meta.server import MetaServer

    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_metachaos_")
    install(meta_chaos_schedule(seed))
    meta = MetaServer(data_dir=os.path.join(data_dir, "meta"))
    addr = meta.start()
    writer = Session(data_dir=data_dir, meta_addr=addr,
                     state_store="hummock", checkpoint_frequency=2)
    control = Session(checkpoint_frequency=2)
    reader: Optional[Session] = None
    try:
        for s in (writer, control):
            s.run_sql("CREATE TABLE mt (k BIGINT, v BIGINT)")
            s.run_sql("CREATE MATERIALIZED VIEW mq AS SELECT k, "
                      "count(*) AS n, sum(v) AS s FROM mt GROUP BY k")
        for i in range(ticks):
            stmt = f"INSERT INTO mt VALUES ({i % 3}, {i * 10})"
            writer.run_sql(stmt)
            control.run_sql(stmt)
            writer.tick()
            control.tick()
        writer.flush()
        control.flush()
        # a reader attaching OVER the slow link still converges: its
        # catalog load + snapshot adoption are plain meta RPCs
        reader = Session(data_dir=data_dir, meta_addr=addr,
                         role="serving")
        got = sorted(reader.run_sql("SELECT * FROM mq"))
        want = sorted(control.run_sql("SELECT * FROM mq"))
        assert got == want, (
            f"reader diverged under meta-link delay: {got[:5]} vs "
            f"{want[:5]}")
        report = ConsistencyAuditor(writer).audit(control=control)
        report.assert_ok()
        # a slow meta link is NOT a dead writer: with every renewal
        # delayed (schedule rule 2) the lease must still be held at
        # term 1 with zero failovers — latency degrades tick rate, never
        # leadership (docs/control-plane.md "Election")
        lease = writer.meta.lease_info()
        assert lease.get("term") == 1 and not lease.get("failovers"), (
            f"slow meta link caused a spurious failover: {lease}")
        injections = dict(plane().injections)
        # replay compares ONLY the deterministic request stream (key
        # exactly META_LINK): the wall-clock-paced side streams —
        # lease heartbeats (#clease), subscription dials (#csub),
        # notification-driven pin reports (#cpins) — legitimately vary
        # run to run
        trace = {k: v for k, v in _collect_trace(data_dir).items()
                 if k == META_LINK}
        return {
            "scenario": "meta_link_delay", "seed": seed,
            "rows": len(got),
            "injections": injections,
            "meta_requests": writer.meta.stats["requests"],
            "lease_term": lease.get("term"),
            "failovers": lease.get("failovers", 0),
            "audit": {k: v.get("ok") for k, v in report.checks.items()},
            "trace": trace,
        }
    finally:
        install(None)
        if reader is not None:
            reader.close()
        writer.close()
        control.close()
        meta.stop()


_FAILOVER_TABLE_DDL = "CREATE TABLE ft (k BIGINT, v BIGINT)"
_FAILOVER_MV_DDL = ("CREATE MATERIALIZED VIEW fmv AS SELECT k, "
                    "count(*) AS n, sum(v) AS s FROM ft GROUP BY k")


def failover_chaos_schedule(seed: int) -> ChaosSchedule:
    """Seeded chaos the DOOMED writer of ``run_failover`` conducts
    under. The meta-RPC delays are confined to the first 20 frames of
    the deterministic request stream — a window that closes during DDL
    (before the insert loop, whose tail is truncated at the wall-clock
    SIGKILL instant), so the injection trace replays identically even
    though the kill lands at a different frame each run. The second
    rule delays EVERY lease heartbeat; those ride their own
    ``meta#clease`` stream (wall-clock-paced, excluded from the replay
    comparison) and must not expire the lease while the writer lives."""
    from .meta.client import META_LINK
    return ChaosSchedule(seed, [
        ChaosRule(kind="delay", link=META_LINK, prob=0.5, delay_ms=2.0,
                  frames=[0, 20]),
        ChaosRule(kind="delay", link=META_LINK, types=["lease.renew"],
                  prob=1.0, delay_ms=1.0),
    ], name="failover_writer_chaos")


def _failover_writer_main(data_dir: str, addr: str, seed: int) -> int:
    """Entry for the doomed-writer CHILD process of ``run_failover``
    (spawned as ``sim --failover-writer DIR ADDR SEED`` and SIGKILLed
    mid-stream — kill -9, no demotion, no goodbye). Chaos installs HERE
    only; the parent's standbys run chaos-free. Reports readiness and
    every committed epoch on stdout so the parent can time the kill."""
    install(failover_chaos_schedule(seed), trace_path=os.path.join(
        data_dir, "chaos_trace_writer.jsonl"))
    w = Session(data_dir=data_dir, meta_addr=addr, state_store="hummock",
                checkpoint_frequency=2)
    w.run_sql(_FAILOVER_TABLE_DDL)
    w.run_sql(_FAILOVER_MV_DDL)
    print("WRITER_READY", flush=True)
    i = 0
    while True:
        w.run_sql(f"INSERT INTO ft VALUES ({i % 5}, {i})")
        w.tick()
        i += 1
        print(f"WRITER_COMMITTED {w.store.committed_epoch}", flush=True)


def run_failover(seed: int = 7, data_dir: Optional[str] = None,
                 lease_ttl_s: float = 1.0,
                 kill_after_commits: int = 3,
                 tail_inserts: int = 6) -> dict:
    """Leader-failover acceptance scenario (docs/control-plane.md,
    ISSUE 18): SIGKILL the writer PROCESS mid-stream while it conducts
    under seeded chaos → the meta server's TTL detector pushes one
    ``leader_down`` → two chaos-free standbys race ``lease.acquire`` at
    term+1 → exactly one promotes in place and resumes conduction, with
    NO operator action. The monitor (a plain MetaClient subscribed to
    the barrier/checkpoint/leader channels) is the split-brain probe:
    conduction terms never move backwards, per-term epochs and committed
    epochs stay strictly increasing across the handover. Exactly-once is
    audited bit-exact: the committed table rows replayed into a fresh
    in-process control must yield the same MV — the killed writer's
    in-flight epoch either committed once or left no trace."""
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import time as _time

    from .common.audit import ConsistencyAuditor
    from .meta.client import META_LINK, MetaClient
    from .meta.server import MetaServer

    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_failover_")
    meta = MetaServer(data_dir=os.path.join(data_dir, "meta"),
                      lease_ttl_s=lease_ttl_s)
    addr = meta.start()

    mon = MetaClient(addr, session_id="failover-monitor")
    events: List[tuple] = []
    ev_lock = threading.Lock()

    def _watch(channel: str) -> None:
        def cb(_version, info, _ch=channel):
            with ev_lock:
                events.append((_ch, _time.monotonic(), info))
        mon.notifications.subscribe(channel, cb)

    for ch in ("barrier", "checkpoint", "leader", "leader_down"):
        _watch(ch)

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
        "JAX_PLATFORMS", "cpu"))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    child_err = open(os.path.join(data_dir, "writer.stderr"), "w",
                     encoding="utf-8")
    child = subprocess.Popen(
        [_sys.executable, "-m", "risingwave_tpu.sim",
         "--failover-writer", data_dir, addr, str(seed)],
        stdout=subprocess.PIPE, stderr=child_err, text=True, env=env)
    state = {"ready": False, "committed": 0}

    def _drain() -> None:
        # a dedicated drain keeps the child's stdout pipe from filling
        # (a blocked writer would stop heartbeating and die of TTL
        # expiry BEFORE the kill — a different scenario)
        for line in child.stdout:
            line = line.strip()
            if line == "WRITER_READY":
                state["ready"] = True
            elif line.startswith("WRITER_COMMITTED"):
                state["committed"] = int(line.split()[1])

    threading.Thread(target=_drain, daemon=True).start()

    def _wait(cond, timeout_s: float, what: str) -> None:
        deadline = _time.monotonic() + timeout_s
        while not cond():
            if child.poll() is not None:
                raise AssertionError(
                    f"doomed writer died early (rc={child.returncode}) "
                    f"waiting for {what}; see "
                    f"{data_dir}/writer.stderr")
            if _time.monotonic() >= deadline:
                raise AssertionError(f"timed out waiting for {what}")
            _time.sleep(0.02)

    standbys: List[Session] = []
    control: Optional[Session] = None
    try:
        _wait(lambda: state["ready"], 180.0, "writer DDL")
        # standbys attach once the catalog exists; chaos-free, serving
        # reads until the election
        standbys = [Session(data_dir=data_dir, meta_addr=addr,
                            role="standby", checkpoint_frequency=2)
                    for _ in range(2)]
        _wait(lambda: state["committed"] >= kill_after_commits,
              120.0, f"{kill_after_commits} committed epochs")
        killed_at = state["committed"]
        kill_t = _time.monotonic()
        child.kill()
        child.wait(timeout=30)

        def _promoted():
            return next((s for s in standbys
                         if s._leadership["promotions"]), None)

        deadline = kill_t + lease_ttl_s * 10 + 60
        while _promoted() is None and _time.monotonic() < deadline:
            _time.sleep(0.02)
        promoted = _promoted()
        assert promoted is not None, (
            "no standby promoted after the writer kill: "
            f"{[s._leadership for s in standbys]}")
        mttr_ms = (_time.monotonic() - kill_t) * 1e3
        # let every candidate's election thread settle before judging
        # the race — a loser mid-acquire is not yet a loser
        _wait_settled = _time.monotonic() + 30
        while any(s._election_busy for s in standbys) \
                and _time.monotonic() < _wait_settled:
            _time.sleep(0.02)
        assert sum(s._leadership["promotions"] for s in standbys) == 1, (
            "split brain: more than one standby promoted: "
            f"{[s._leadership for s in standbys]}")
        loser = next(s for s in standbys if s is not promoted)
        assert loser.role == "serving", loser.role

        # the promoted writer resumes conduction under term 2 — and the
        # losing standby keeps serving reads throughout
        for j in range(tail_inserts):
            promoted.run_sql(
                f"INSERT INTO ft VALUES ({j % 5}, {10_000 + j})")
            promoted.tick()
        promoted.flush()
        rows = promoted.run_sql("SELECT k, v FROM ft")
        vs = [r[1] for r in rows]
        assert len(vs) == len(set(vs)), (
            "duplicate rows survived the failover: an epoch applied "
            "twice")
        assert len(loser.run_sql("SELECT k, n, s FROM fmv")) > 0

        # exactly-once, bit-exact: the committed rows replayed into a
        # fresh control must rebuild the same MV state the promoted
        # writer recovered + maintained across the handover
        control = Session(checkpoint_frequency=2)
        control.run_sql(_FAILOVER_TABLE_DDL)
        control.run_sql(_FAILOVER_MV_DDL)
        ordered = sorted(rows, key=lambda r: r[1])
        for off in range(0, len(ordered), 8):
            chunk = ordered[off:off + 8]
            control.run_sql("INSERT INTO ft VALUES " + ", ".join(
                f"({k}, {v})" for k, v in chunk))
            control.tick()
        control.flush()
        report = ConsistencyAuditor(promoted).audit(
            control=control, mv_names=["fmv"])
        report.assert_ok()

        # -- the monitor's split-brain probe --------------------------------
        with ev_lock:
            evs = list(events)
        downs = [e for e in evs if e[0] == "leader_down"]
        assert len(downs) == 1 and downs[0][2]["term"] == 1, downs
        leader_terms = [int(e[2]["term"]) for e in evs
                        if e[0] == "leader"]
        assert leader_terms == sorted(set(leader_terms)), (
            f"leader terms not strictly increasing: {leader_terms}")
        assert [e[2]["reason"] for e in evs
                if e[0] == "leader"].count("election") == 1
        pub_terms = [int(e[2]["term"]) for e in evs
                     if e[0] in ("barrier", "checkpoint")
                     and e[2].get("term") is not None]
        assert all(a <= b for a, b in zip(pub_terms, pub_terms[1:])), (
            f"conduction terms moved backwards: {pub_terms}")
        by_term: Dict[int, List[int]] = {}
        for e in evs:
            if e[0] == "barrier" and e[2].get("term") is not None:
                by_term.setdefault(int(e[2]["term"]), []).append(
                    int(e[2]["epoch"]))
        for term, epochs in by_term.items():
            assert all(a < b for a, b in zip(epochs, epochs[1:])), (
                f"term {term} epochs not strictly increasing: {epochs}")
        commits = [int(e[2]["committed_epoch"]) for e in evs
                   if e[0] == "checkpoint"]
        assert all(a < b for a, b in zip(commits, commits[1:])), (
            f"committed epochs not strictly increasing: {commits}")
        detect_ms = (downs[0][1] - kill_t) * 1e3
        ckpt_times = [e[1] for e in evs if e[0] == "checkpoint"]
        gaps = [(b - a) * 1e3
                for a, b in zip(ckpt_times, ckpt_times[1:])]

        info = mon.lease_info()
        assert info["failovers"] == 1 and info["term"] == 2, info
        trace = {k: v for k, v in _collect_trace(data_dir).items()
                 if k == META_LINK}
        return {
            "scenario": "leader_failover", "seed": seed,
            "lease_ttl_s": lease_ttl_s,
            "killed_at_commit": killed_at,
            "rows": len(rows),
            "terms": sorted(by_term),
            "failovers": info["failovers"],
            "detect_ms": round(detect_ms, 3),
            "mttr_ms": round(mttr_ms, 3),
            "unavail_ms": round(max(gaps), 3) if gaps else None,
            "gap_samples_ms": [round(g, 3) for g in gaps],
            "elections_lost": sum(s._leadership["elections_lost"]
                                  for s in standbys),
            "audit": {k: v.get("ok") for k, v in report.checks.items()},
            "trace": trace,
        }
    finally:
        mon.close()
        for s in standbys:
            s.close()
        if control is not None:
            control.close()
        if child.poll() is None:
            child.kill()
        child_err.close()
        meta.stop()


def run_udf_soak(duration_s: float = 45.0, seed: int = 5,
                 data_dir: Optional[str] = None,
                 kill_every: int = 6,
                 min_ticks: int = 12) -> dict:
    """Soak seed (ROADMAP item 5's standing gauntlet, first brick): RPC
    chaos on the worker exchange links (dup + reorder — absorbed by the
    seq layer, no recovery expected) + periodic UDF-server SIGKILLs +
    concurrent serving readers (one of them crossing the UDF boundary),
    all live for ``duration_s``, then a bit-exact audit against a
    no-chaos control. Returns a SCHEMA-STABLE numeric record."""
    import tempfile
    import threading
    import time as _time

    from .common.audit import ConsistencyAuditor
    from .common.config import FaultConfig, UdfConfig
    from .frontend.build import BuildConfig
    from .udf.client import udf_plane

    _ensure_udf("soak_tax", _chaos_tax)
    data_dir = data_dir or tempfile.mkdtemp(prefix="rwtpu_udfsoak_")
    udf_plane().configure(UdfConfig(call_timeout_s=5.0, max_retries=4),
                          trace_dir=data_dir)
    base = dict(udf_plane().snapshot())
    schedule = ChaosSchedule(seed, [
        ChaosRule(kind="duplicate", link="w0<->w1", types=["exg_data"],
                  prob=0.2),
        ChaosRule(kind="delay", link="w0<->w1",
                  types=["exg_data:chunk"], prob=0.2, delay_frames=2),
    ], name="udf_soak")
    fc = FaultConfig(worker_epoch_timeout_s=60.0,
                     exchange_keepalive_s=0.0)
    sim = SimCluster(data_dir, seed=seed, kill_rate=0.0, workers=2,
                     chaos=schedule, checkpoint_frequency=4,
                     source_chunk_capacity=64, fault_config=fc,
                     config=BuildConfig(fragment_parallelism=2))
    control = None
    stop = threading.Event()
    reader_stats = {"queries": 0, "errors": 0}

    def reader() -> None:
        while not stop.is_set():
            try:
                sim.session.run_sql(
                    "SELECT auction, num FROM q WHERE auction >= 0")
                sim.session.run_sql("SELECT k, soak_tax(v) FROM ut")
                reader_stats["queries"] += 2
            except Exception:  # noqa: BLE001 - counted, asserted == 0
                reader_stats["errors"] += 1
            _time.sleep(0.05)

    t0 = _time.monotonic()
    ticks = 0
    threads = []
    try:
        control = Session(seed=42, source_chunk_capacity=64,
                          checkpoint_frequency=4)
        for sess in (sim.session, control):
            sess.run_sql(_BID_DDL)
            sess.run_sql(
                "CREATE MATERIALIZED VIEW q AS SELECT auction, "
                "count(*) AS num FROM bid GROUP BY auction")
            sess.run_sql(_UDF_T_DDL)
            sess.run_sql("CREATE MATERIALIZED VIEW mu AS "
                         "SELECT k, soak_tax(v) AS tv FROM ut")
        assert "q" in sim.session._spanning_specs, \
            "soak MV did not deploy as a spanning graph"
        threads = [threading.Thread(target=reader, daemon=True)]
        for t in threads:
            t.start()
        while ticks < min_ticks or \
                _time.monotonic() - t0 < duration_s:
            sim.run_sql(
                f"INSERT INTO ut VALUES ({ticks + 1}, {ticks * 11})")
            control.run_sql(
                f"INSERT INTO ut VALUES ({ticks + 1}, {ticks * 11})")
            sim.tick()
            control.tick()
            ticks += 1
            if kill_every and ticks % kill_every == 0:
                udf_plane().kill_server()
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sim.verify_against(control, ["q", "mu"])
        report = ConsistencyAuditor(sim.session).audit(control=control)
        report.assert_ok()
        wall = _time.monotonic() - t0
        stats = udf_plane().snapshot()
        # exchange-link injections happen in the WORKER processes'
        # planes; the session federates their snapshots in metrics()
        chaos_m = sim.session.metrics().get("chaos", {})
        inj = dict(chaos_m.get("injections") or {})
        for wst in (chaos_m.get("workers") or {}).values():
            for k, v in (wst.get("injections") or {}).items():
                inj[k] = inj.get(k, 0) + v
        return {
            "seed": seed,
            "duration_s": round(wall, 3),
            "ticks": ticks,
            "rows_per_sec": round(
                ticks * 64 / wall, 3) if wall > 0 else 0.0,
            "udf_calls": stats["calls"] - base["calls"],
            "udf_spawns": stats["spawns"] - base["spawns"],
            "udf_respawns": stats["respawns"] - base["respawns"],
            "udf_timeouts": stats["timeouts"] - base["timeouts"],
            "udf_stale_drops": stats["stale_replies_dropped"]
            - base["stale_replies_dropped"],
            "reader_queries": reader_stats["queries"],
            "reader_errors": reader_stats["errors"],
            "chaos_injections": sum(inj.values()),
            "mv_rows": len(sim.mv_rows("q")),
            "audit_ok": int(all(v.get("ok")
                                for v in report.checks.values())),
        }
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sim.close()
        if control is not None:
            control.close()


def main(argv=None) -> int:
    """CLI for replaying seeds: ``python -m risingwave_tpu.sim
    --netsplit q5_exchange_partition --seed 7 [--replay]`` or
    ``--sweep [--sites a,b]`` (docs/robustness.md)."""
    import argparse
    import tempfile
    ap = argparse.ArgumentParser()
    ap.add_argument("--netsplit", choices=sorted(NETSPLIT_SCENARIOS))
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--replay", action="store_true",
                    help="run the scenario twice and assert the "
                         "injection traces are identical")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--spanning-sweep", action="store_true")
    ap.add_argument("--traffic-spike", action="store_true",
                    help="run the elastic-scaling acceptance scenario: "
                         "seeded load spike → autoscaler live-rescales "
                         "2→4 → no flap on subside → audit green "
                         "(docs/scaling.md)")
    ap.add_argument("--sites", default=None,
                    help="comma-separated failpoint subset for --sweep")
    ap.add_argument("--udf-chaos", action="store_true",
                    help="run the UDF-link chaos scenario: seeded "
                         "drop/delay/duplicate on s->udf plus a server "
                         "SIGKILL mid-run, audited bit-exact against a "
                         "no-chaos control (docs/robustness.md)")
    ap.add_argument("--meta-chaos", action="store_true",
                    help="run the meta-link latency scenario: a writer "
                         "attached to a standalone MetaServer plus a "
                         "serving reader over a seeded-delayed RPC "
                         "link, audited bit-exact against an "
                         "in-process control (docs/control-plane.md)")
    ap.add_argument("--failover", action="store_true",
                    help="run the leader-failover acceptance scenario: "
                         "kill -9 the writer process mid-stream under "
                         "seeded chaos → a standby auto-promotes within "
                         "the lease TTL with no operator action, "
                         "exactly-once audited, split-brain probe green "
                         "(docs/control-plane.md)")
    ap.add_argument("--failover-writer", nargs=3,
                    metavar=("DIR", "ADDR", "SEED"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--udf-soak", action="store_true",
                    help="run the soak seed: RPC chaos + UDF-server "
                         "kills + serving readers live together, "
                         "auditor green; emits the BENCH_partial-shaped "
                         "udf_soak record")
    ap.add_argument("--duration", type=float, default=45.0,
                    help="--udf-soak wall-clock duration in seconds")
    args = ap.parse_args(argv)
    if args.failover_writer:
        d, addr, s = args.failover_writer
        return _failover_writer_main(d, addr, int(s))
    if args.netsplit:
        r1 = run_netsplit(args.netsplit, seed=args.seed,
                          data_dir=tempfile.mkdtemp(prefix="rwtpu_ns1_"))
        print(json.dumps({k: r1[k] for k in
                          ("scenario", "seed", "recovered", "audit")},
                         indent=2))
        if args.replay:
            r2 = run_netsplit(args.netsplit, seed=args.seed,
                              data_dir=tempfile.mkdtemp(
                                  prefix="rwtpu_ns2_"))
            assert r1["trace"] == r2["trace"], (
                "seeded replay diverged:\n"
                f"run1: {r1['trace']}\nrun2: {r2['trace']}")
            print(f"replay OK: {sum(len(v) for v in r1['trace'].values())}"
                  " injections reproduced identically")
    if args.sweep:
        sites = args.sites.split(",") if args.sites else None
        res = crash_point_sweep(tempfile.mkdtemp(prefix="rwtpu_sweep_"),
                                sites=sites, seed=args.seed)
        print(json.dumps(res, indent=2))
    if args.spanning_sweep:
        res = crash_point_sweep_spanning(
            tempfile.mkdtemp(prefix="rwtpu_span_"))
        print(json.dumps(res, indent=2))
    if args.traffic_spike:
        res = run_traffic_spike(
            seed=args.seed,
            data_dir=tempfile.mkdtemp(prefix="rwtpu_spike_"))
        print(json.dumps(res, indent=2, default=str))
    if args.udf_chaos:
        r1 = run_udf_chaos(seed=args.seed,
                           data_dir=tempfile.mkdtemp(
                               prefix="rwtpu_udfc1_"))
        print(json.dumps({k: r1[k] for k in
                          ("scenario", "seed", "respawns", "timeouts",
                           "injections", "audit")}, indent=2))
        if args.replay:
            r2 = run_udf_chaos(seed=args.seed,
                               data_dir=tempfile.mkdtemp(
                                   prefix="rwtpu_udfc2_"))
            assert r1["trace"] == r2["trace"], (
                "seeded udf-chaos replay diverged:\n"
                f"run1: {r1['trace']}\nrun2: {r2['trace']}")
            print(f"replay OK: "
                  f"{sum(len(v) for v in r1['trace'].values())} "
                  "injections reproduced identically")
    if args.meta_chaos:
        r1 = run_meta_chaos(seed=args.seed,
                            data_dir=tempfile.mkdtemp(
                                prefix="rwtpu_metac1_"))
        print(json.dumps({k: r1[k] for k in
                          ("scenario", "seed", "rows", "injections",
                           "audit")}, indent=2))
        if args.replay:
            r2 = run_meta_chaos(seed=args.seed,
                                data_dir=tempfile.mkdtemp(
                                    prefix="rwtpu_metac2_"))
            assert r1["trace"] == r2["trace"], (
                "seeded meta-chaos replay diverged:\n"
                f"run1: {r1['trace']}\nrun2: {r2['trace']}")
            print(f"replay OK: "
                  f"{sum(len(v) for v in r1['trace'].values())} "
                  "injections reproduced identically")
    if args.failover:
        r1 = run_failover(seed=args.seed,
                          data_dir=tempfile.mkdtemp(
                              prefix="rwtpu_fo1_"))
        print(json.dumps({k: r1[k] for k in
                          ("scenario", "seed", "killed_at_commit",
                           "terms", "failovers", "detect_ms",
                           "mttr_ms", "unavail_ms", "rows", "audit")},
                         indent=2))
        if args.replay:
            r2 = run_failover(seed=args.seed,
                              data_dir=tempfile.mkdtemp(
                                  prefix="rwtpu_fo2_"))
            assert r1["trace"] == r2["trace"], (
                "seeded failover replay diverged:\n"
                f"run1: {r1['trace']}\nrun2: {r2['trace']}")
            print(f"replay OK: "
                  f"{sum(len(v) for v in r1['trace'].values())} "
                  "injections reproduced identically")
    if args.udf_soak:
        res = run_udf_soak(duration_s=args.duration, seed=args.seed)
        print(json.dumps({"phase": "udf_soak", "record": res}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
