from .checkpoint import (latest_step, load_checkpoint, save_checkpoint)
from .optimizer import OptimizerConfig, make_optimizer
from .sharding import (ShardingRules, batch_specs, named, opt_state_specs,
                       param_specs)
from .step import TrainConfig, make_serve_steps, make_train_step

__all__ = [
    "latest_step", "load_checkpoint", "save_checkpoint",
    "OptimizerConfig", "make_optimizer",
    "ShardingRules", "batch_specs", "named",
    "opt_state_specs", "param_specs",
    "TrainConfig", "make_serve_steps", "make_train_step",
]
