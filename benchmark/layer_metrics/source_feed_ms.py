"""source_feed_ms — on the executor path, the time of a barrier before its
ledger record opens: ``Session.tick()`` first has every source hand over
its chunks (the host NEXmark generator draws a chunk with numpy and copies
its columns to the device, ``chunks_per_tick`` times) and only then
injects the barrier. Read as ``pre_barrier_ms`` is — the barrier's
host-clock time around ``tick()`` minus the ledger's record of it —
median over the window's barriers; the two differ in the layer whose work
falls there, so each cell lists the one that names its layer."""

from benchmark.layer_metrics.pre_barrier_ms import read  # noqa: F401
