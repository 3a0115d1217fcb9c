"""executor_unowned_ms — per barrier, the job tasks' time that no executor
owns: the ``actor.run`` spans minus every operator's ``<identity>.chunks``
roll-up and ``<identity>.barrier`` span under the same ``barrier.collect``
— the generator chain between the executors, the queues, the tasks
``barrier_align`` polls its inputs from, the changelog bus. Never below
-0.5: the steps are disjoint and lie inside the tasks' spans. Median over
the covered window barriers; nothing for a program without ``actor.run``.
``operator_busy_ms`` + this + (``collect_ms`` - ``actor_run_ms``) is
``collect_ms``. The counts the operators' spans carry (every arg of a
``.chunks`` roll-up, a ``.barrier`` span and ``shard.split`` but ``node``;
two executors of one identity summed) are printed on a line of their own,
medians over the same barriers."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median


def per_barrier(spans: list) -> float:
    (collect,) = ps.named(spans, "barrier.collect", "executor_unowned_ms")
    under = [s for s in spans if s["parent"] == collect["id"]]
    own = [s for s in under if s["name"].endswith((".chunks", ".barrier"))]
    if not own:
        raise LookupError(
            f"executor_unowned_ms: no operator span under barrier.collect "
            f"in epoch {spans[0]['epoch']}")
    return ps.ms([s for s in under if s["name"] == actor_run_ms.NAME]) \
        - ps.ms(own)


def operator_counts(covered: list) -> dict:
    per_barrier = []
    for _b, spans in covered:
        (collect,) = ps.named(spans, "barrier.collect", "executor_unowned_ms")
        by_name: dict = {}
        for s in spans:
            if s["parent"] == collect["id"] and s["name"] != actor_run_ms.NAME:
                by_name.setdefault(s["name"], []).append(s)
        per_barrier.append(by_name)
    out = {}
    for name in sorted({n for by_name in per_barrier for n in by_name}):
        found = [by_name.get(name, []) for by_name in per_barrier]
        args = {a for spans in found for s in spans
                for a in s.get("args") or {}} - {"node"}
        if args:
            out[name] = actor_run_ms.counts(found, tuple(sorted(args)))
    return out


def read(ctx: dict):
    if actor_run_ms.find(ctx, "executor_unowned_ms",
                         (actor_run_ms.NAME,)) is None:
        return None
    covered = ps.window(ctx)
    values = [per_barrier(spans) for _b, spans in covered]
    print(json.dumps({"operators": operator_counts(covered)}), flush=True)
    return median(values)
