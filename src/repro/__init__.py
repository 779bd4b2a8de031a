"""repro — "Dataset Management Platform for Machine Learning" (TDCommons
5690, Feb 2023) reproduced as a JAX framework that trains on TPU.

Subpackages:
  core     the paper's platform (storage engine, versioning, dataset
           manager, ACL, transforms, workflow manager, lineage, revocation)
  data     ML pipeline components + sharded resumable loader
  models   the 10 assigned architectures (dense/MoE/SSM/hybrid/enc-dec/VLM)
  kernels  Pallas TPU kernels (flash attention, SSD, RG-LRU) + oracles
  train    optimizers, sharding rules, train step, platform checkpointing
  serve    batched serving engine
  launch   device mesh, train and serve drivers
  configs  architecture registry (--arch ids)
"""

__version__ = "0.1.0"

from .platform import DatasetHandle, Platform, VersionHandle  # noqa: E402

__all__ = ["Platform", "DatasetHandle", "VersionHandle", "__version__"]
