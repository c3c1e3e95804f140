"""``device_idle`` in the cells of a routed model with latent attention
(``deepseek-v2-lite.collm.rag``), where a later change to one family's
path can move the other's: the reading is the same, and a metric of its
own keeps those cells' history apart."""
from bench.metrics.device_idle import read  # noqa: F401
