"""state_delta_ms — the checkpoint's host-side state-table delta: the
``agg.state_delta`` span (dirty groups found, fetched, encoded and staged;
inside ``HashAggExecutor._checkpoint_to_state_table``, which both paths
call) plus, on the co-scheduled path, ``cosched.restack`` (the job's state
taken out of the stacked group state and put back). Median over the
covered CHECKPOINT barriers of the window."""

from benchmark import program_spans as ps


def per_barrier(spans: list) -> float:
    return ps.ms(ps.named(spans, "agg.state_delta", "state_delta_ms")) \
        + ps.ms([s for s in spans if s["name"] == "cosched.restack"])


def read(ctx: dict):
    return ps.median_over(ctx, per_barrier, checkpoint_only=True)
