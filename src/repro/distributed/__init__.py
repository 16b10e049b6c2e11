from .sharding import (  # noqa: F401
    AxisRules,
    DEFAULT_RULES,
    auto_axes,
    axis_ctx,
    constrain,
    resolve_pspec,
    param_shardings,
    shard_map,
    use_rules,
)
