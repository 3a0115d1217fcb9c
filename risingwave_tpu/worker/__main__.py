"""``python -m risingwave_tpu.worker`` — worker-node entry point
(reference: the compute-node binary, src/cmd/src/bin/compute_node.rs)."""

from .host import main

main()
