# Pallas TPU kernels for the perf-critical compute of the assigned
# architectures (the paper itself — a dataset-management platform — has no
# kernel-level contribution; these serve its training/serving consumers):
#   flash_attention: GQA + sliding-window + softcap + packed-segment flash
#   ssd:             Mamba-2 chunked state-space-duality scan
#   rglru:           RecurrentGemma RG-LRU linear recurrence
# Each has kernel.py (pl.pallas_call + BlockSpec), ops.py (dispatching jit
# wrapper: "auto" picks the kernel on a TPU and the XLA path elsewhere;
# "pallas" raises off the TPU), and ref.py (pure-jnp oracle).

from . import flash_attention, rglru, ssd

__all__ = ["flash_attention", "ssd", "rglru"]
