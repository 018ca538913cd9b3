from repro.sharding.rules import (ShardingRules, active_rules, constrain,
                                  constrain_heads, use_rules)

__all__ = ["ShardingRules", "active_rules", "constrain", "constrain_heads",
           "use_rules"]
