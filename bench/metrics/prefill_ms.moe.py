"""``prefill_ms`` in the cells of a routed model with latent attention
(``deepseek-v2-lite.collm.rag``): a closed-loop batch reports no TTFT, so
there prefill moves the throughput, as ``prefill_ms.batch`` does for the
llama block.  The reading is the same."""
from bench.metrics.prefill_ms import read  # noqa: F401
