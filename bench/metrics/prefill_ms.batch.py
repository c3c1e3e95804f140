"""``prefill_ms`` in a cell that reports no TTFT: in a batch, prefill time
is taken from every slot's decoding, so there it moves the throughput.  A
metric names the one end-to-end metric it moves, and every cell it lists
reports that metric, so the quantity is split by cell; the reading is
the same."""
from bench.metrics.prefill_ms import read  # noqa: F401
