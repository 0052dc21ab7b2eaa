from repro.sharding.spec import (  # noqa: F401
    LOCAL_CTX,
    MULTI_POD_RULES,
    Rules,
    SINGLE_POD_RULES,
    ShardCtx,
    rules_for_mesh,
)
