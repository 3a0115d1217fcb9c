"""System catalogs: pg_catalog / information_schema / rw_catalog views.

Counterpart of the reference's frontend system catalogs
(reference: src/frontend/src/catalog/system_catalog/ — pg_catalog,
information_schema and rw_catalog tables BI tools introspect through).
Served as constant VALUES plans materialized from the live catalog at
plan time — a batch SELECT over them reads a consistent snapshot, the
same way the reference serves them from the frontend catalog cache.

Two tiers of relations:

* catalog-backed (pg_tables, rw_relations, …) — derived from the
  Catalog alone, available everywhere a Planner runs.
* session-backed (rw_barrier_history, rw_actors, rw_hbm_ledger, …) —
  the live telemetry estate, materialized from the owning Session at
  plan time under the session API lock, so one SELECT reads one
  consistent snapshot of the cluster (reference: rw_catalog's
  meta-backed system tables, e.g. rw_fragments / rw_actors served from
  the meta client). In session-less contexts (``DESCRIBE``, DDL
  replay) they plan with their schema and zero rows.

System relations are deliberately EXCLUDED from the serving plan
cache (frontend/serving.py): their "data" is whatever the telemetry
says right now, so a cached plan over yesterday's VALUES would be a
stale lie that no data_version seqlock invalidates.
"""

from __future__ import annotations

import json
from typing import Optional

from ..common.types import BOOL, FLOAT64, INT64, Schema, VARCHAR

#: relation name (lowercase, optionally qualified) → builder(catalog)
_SCHEMA_STR = "public"


def _pg_tables(catalog):
    schema = Schema.of(("schemaname", VARCHAR), ("tablename", VARCHAR),
                       ("tableowner", VARCHAR))
    rows = [(_SCHEMA_STR, name, "root") for name in catalog.tables]
    rows += [(_SCHEMA_STR, name, "root") for name in catalog.sources]
    return schema, rows


def _pg_matviews(catalog):
    schema = Schema.of(("schemaname", VARCHAR), ("matviewname", VARCHAR),
                       ("definition", VARCHAR))
    rows = [(_SCHEMA_STR, name, mv.definition or "")
            for name, mv in catalog.mvs.items()
            if not name.startswith("__idx_")]
    return schema, rows


def _info_tables(catalog):
    schema = Schema.of(("table_schema", VARCHAR), ("table_name", VARCHAR),
                       ("table_type", VARCHAR))
    rows = [(_SCHEMA_STR, n, "BASE TABLE") for n in catalog.tables]
    rows += [(_SCHEMA_STR, n, "SYSTEM SOURCE") for n in catalog.sources]
    rows += [(_SCHEMA_STR, n, "MATERIALIZED VIEW") for n in catalog.mvs
             if not n.startswith("__idx_")]
    return schema, rows


def _info_columns(catalog):
    schema = Schema.of(
        ("table_schema", VARCHAR), ("table_name", VARCHAR),
        ("column_name", VARCHAR), ("ordinal_position", INT64),
        ("data_type", VARCHAR))
    rows = []
    for reg in (catalog.tables, catalog.sources, catalog.mvs):
        for name, d in reg.items():
            n_vis = getattr(d, "n_visible", len(d.schema))
            for i, f in enumerate(d.schema):
                if i >= n_vis or f.name.startswith("_"):
                    continue
                rows.append((_SCHEMA_STR, name, f.name, i + 1,
                             f.type.kind.value))
    return schema, rows


def _rw_relations(catalog):
    schema = Schema.of(("name", VARCHAR), ("kind", VARCHAR))
    rows = [(n, "table") for n in catalog.tables]
    rows += [(n, "source") for n in catalog.sources]
    rows += [(n, "materialized view") for n in catalog.mvs
             if not n.startswith("__idx_")]
    rows += [(n, "sink") for n in catalog.sinks]
    rows += [(n, "index") for n in catalog.indexes]
    return schema, rows


# -- session-backed telemetry relations ---------------------------------------
#
# Builders take (catalog, session); session=None (DESCRIBE, recovery
# replay) plans the schema with zero rows. Stage columns ARE
# barrier_ledger.ALL_STAGES, in its order: the waterfall reads left→right.

def _rw_barrier_history(catalog, session):
    from ..common.barrier_ledger import ALL_STAGES
    schema = Schema.of(
        ("epoch", INT64), ("checkpoint", BOOL), ("result", VARCHAR),
        ("injected_at", FLOAT64), ("total_ms", FLOAT64),
        ("tick_ms", FLOAT64), ("compiles", INT64),
        *((f"{s}_ms", FLOAT64) for s in ALL_STAGES),
        ("workers", VARCHAR))
    if session is None:
        return schema, []
    rows = []
    for rec in session._barrier_ledger.history():
        stages = rec.get("stages", {})
        rows.append((
            rec["epoch"], bool(rec["checkpoint"]), rec.get("result"),
            rec.get("injected_at"), rec.get("total_ms"),
            rec.get("tick_ms"), rec.get("compiles", 0),
            *(stages.get(s) for s in ALL_STAGES),
            json.dumps(rec.get("workers", {}), sort_keys=True)))
    return schema, rows


def _rw_barrier_inflight(catalog, session):
    schema = Schema.of(
        ("epoch", INT64), ("checkpoint", BOOL), ("age_ms", FLOAT64),
        ("kind", VARCHAR), ("job", VARCHAR), ("worker", INT64),
        ("fragment", INT64), ("actor", INT64), ("link", VARCHAR),
        ("edge", VARCHAR), ("reason", VARCHAR))
    if session is None:
        return schema, []
    rows = [(f["epoch"], f["checkpoint"], f["age_ms"], f["kind"],
             f["job"], f["worker"], f["fragment"], f["actor"],
             f["link"], f["edge"], f["reason"])
            for f in session.barrier_blame()]
    return schema, rows


def _rw_fragments(catalog, session):
    schema = Schema.of(("job", VARCHAR), ("fragment_id", INT64),
                       ("kind", VARCHAR), ("n_actors", INT64),
                       ("workers", VARCHAR))
    if session is None:
        return schema, []
    rows = []
    for name, spec in sorted(session._spanning_specs.items()):
        placement = spec["placement"]
        for fid, acts in sorted(placement.actors.items()):
            rows.append((name, fid, "spanning", len(acts),
                         ",".join(str(a.worker) for a in acts)))
    for name, spec in sorted(session._remote_specs.items()):
        rows.append((name, 0, "remote", 1,
                     str(spec["worker"].worker_id)))
    for name, job in sorted(session.jobs.items()):
        if getattr(job, "pipeline", None) is not None \
                and name not in session._spanning_specs \
                and name not in session._remote_specs:
            rows.append((name, 0, "local",
                         1 + len(getattr(job, "actors", ())), "-1"))
    return schema, rows


def _rw_actors(catalog, session):
    schema = Schema.of(("job", VARCHAR), ("fragment_id", INT64),
                       ("actor_id", INT64), ("worker", INT64),
                       ("vnode_start", INT64), ("vnode_end", INT64))
    if session is None:
        return schema, []
    rows = []
    for name, spec in sorted(session._spanning_specs.items()):
        placement = spec["placement"]
        for fid, acts in sorted(placement.actors.items()):
            for a in acts:
                rows.append((name, fid, a.actor, a.worker,
                             a.vnode_start, a.vnode_end))
    return schema, rows


def _rw_placements(catalog, session):
    schema = Schema.of(("job", VARCHAR), ("root_worker", INT64),
                       ("workers", VARCHAR), ("n_fragments", INT64),
                       ("n_actors", INT64))
    if session is None:
        return schema, []
    rows = []
    for name, spec in sorted(session._spanning_specs.items()):
        placement = spec["placement"]
        rows.append((name, placement.root_worker,
                     ",".join(str(w) for w in placement.workers()),
                     len(placement.actors),
                     sum(len(a) for a in placement.actors.values())))
    return schema, rows


def _rw_worker_nodes(catalog, session):
    schema = Schema.of(("worker_id", INT64), ("pid", INT64),
                       ("dead", BOOL), ("link", VARCHAR),
                       ("jobs", VARCHAR))
    if session is None:
        return schema, []
    stats = session._federate_worker_stats()
    rows = []
    for w in session.workers:
        jobs = sorted(stats.get(w.worker_id, {}).get("jobs", {}))
        rows.append((w.worker_id,
                     getattr(getattr(w, "proc", None), "pid", None),
                     bool(w.dead), w.link, ",".join(jobs)))
    return schema, rows


def _rw_dispatch_profiles(catalog, session):
    schema = Schema.of(
        ("worker", INT64), ("qualname", VARCHAR), ("calls", INT64),
        ("total_s", FLOAT64), ("mean_ms", FLOAT64), ("max_ms", FLOAT64),
        ("compiles", INT64), ("compile_s", FLOAT64),
        ("complete_mean_ms", FLOAT64))
    if session is None:
        return schema, []
    from ..common.profiling import GLOBAL_PROFILER

    def _rows(wid, dispatch):
        return [(wid, qn, d.get("calls"), d.get("total_s"),
                 d.get("mean_ms"), d.get("max_ms"), d.get("compiles"),
                 d.get("compile_s"), d.get("complete_mean_ms"))
                for qn, d in sorted((dispatch or {}).items())]

    rows = _rows(-1, GLOBAL_PROFILER.snapshot())
    for wid, st in sorted(session._federate_worker_stats().items()):
        rows += _rows(wid, (st.get("profiling") or {}).get("dispatch"))
    return schema, rows


def _rw_hbm_ledger(catalog, session):
    schema = Schema.of(
        ("job", VARCHAR), ("worker", INT64), ("state_bytes", INT64),
        ("flagged", BOOL), ("capacity_bytes", INT64),
        ("used_bytes", INT64), ("headroom_bytes", INT64),
        ("utilization", FLOAT64))
    if session is None:
        return schema, []
    hbm = session.metrics()["profiling"]["hbm"]
    flagged = set(hbm.get("flagged", ()))
    rows = [(name, j.get("worker"), j.get("bytes", 0), name in flagged,
             hbm["capacity_bytes"], hbm["used_bytes"],
             hbm["headroom_bytes"], hbm["utilization"])
            for name, j in sorted(hbm.get("jobs", {}).items())]
    return schema, rows


def _rw_autoscaler_decisions(catalog, session):
    schema = Schema.of(
        ("seq", INT64), ("kind", VARCHAR), ("job", VARCHAR),
        ("reason", VARCHAR), ("from_parallelism", INT64),
        ("to_parallelism", INT64), ("moved_vnodes", INT64),
        ("pause_ms", FLOAT64), ("epoch", INT64))
    if session is None:
        return schema, []
    rows = []
    for i, d in enumerate(session.autoscaler.status()["decisions"]):
        rows.append((i, "decision", d.get("job"), d.get("reason"),
                     d.get("from"), d.get("to"), None, None, None))
    for i, r in enumerate(session._rescale_stats["history"]):
        rows.append((i, "rescale", r.get("job"), None, None,
                     r.get("parallelism"), r.get("moved_vnodes"),
                     r.get("pause_ms"), r.get("epoch")))
    return schema, rows


def _rw_leader_history(catalog, session):
    """Leader-lease acquisition history (meta/server.py persists it):
    one row per term — who held it, when, and why (bootstrap, takeover
    attach, or a TTL-expiry election). In-process meta has no lease, so
    the relation is empty there."""
    schema = Schema.of(
        ("term", INT64), ("holder", VARCHAR), ("acquired_at", FLOAT64),
        ("reason", VARCHAR), ("leaderless_s", FLOAT64),
        ("current", BOOL))
    if session is None:
        return schema, []
    lease_info = getattr(session.meta, "lease_info", None)
    if lease_info is None:
        return schema, []          # in-process meta: no lease surface
    try:
        info = lease_info()
    except Exception:
        return schema, []
    rows = [(h.get("term"), h.get("holder"), h.get("acquired_at"),
             h.get("reason"), h.get("leaderless_s"),
             h.get("term") == info.get("term"))
            for h in info.get("history", ())]
    return schema, rows


_RELATIONS = {
    "pg_tables": _pg_tables,
    "pg_catalog.pg_tables": _pg_tables,
    "pg_matviews": _pg_matviews,
    "pg_catalog.pg_matviews": _pg_matviews,
    "information_schema.tables": _info_tables,
    "information_schema.columns": _info_columns,
    "rw_relations": _rw_relations,
    "rw_catalog.rw_relations": _rw_relations,
}

_SESSION_RELATIONS = {
    "rw_barrier_history": _rw_barrier_history,
    "rw_barrier_inflight": _rw_barrier_inflight,
    "rw_fragments": _rw_fragments,
    "rw_actors": _rw_actors,
    "rw_placements": _rw_placements,
    "rw_worker_nodes": _rw_worker_nodes,
    "rw_dispatch_profiles": _rw_dispatch_profiles,
    "rw_hbm_ledger": _rw_hbm_ledger,
    "rw_autoscaler_decisions": _rw_autoscaler_decisions,
    "rw_leader_history": _rw_leader_history,
}
_SESSION_RELATIONS.update({f"rw_catalog.{n}": b
                           for n, b in list(_SESSION_RELATIONS.items())})

#: every system-relation name (bare + qualified, lowercase) — the
#: serving plane's cache-exclusion check keys on this set
SYSTEM_RELATION_NAMES = frozenset(_RELATIONS) | frozenset(
    _SESSION_RELATIONS)


def system_relation(catalog, name: str,
                    session=None) -> Optional[tuple]:
    """(Schema, rows) for a system view name, or None."""
    key = name.lower()
    builder = _RELATIONS.get(key)
    if builder is not None:
        return builder(catalog)
    builder = _SESSION_RELATIONS.get(key)
    if builder is not None:
        return builder(catalog, session)
    return None
