from .partitioning import (
    ShardingRules,
    activation_rules,
    make_rules,
    param_rules,
    shard,
    set_mesh,
    get_mesh,
)

__all__ = [
    "ShardingRules",
    "activation_rules",
    "make_rules",
    "param_rules",
    "shard",
    "set_mesh",
    "get_mesh",
]
