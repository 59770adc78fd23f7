from .compile_cache import REPO_CACHE_DIR, enable_compile_cache  # noqa: F401
from .fault import (  # noqa: F401
    CircuitBreaker,
    FaultConfig,
    RetryPolicy,
    TrainLoop,
)
from .straggler import (  # noqa: F401
    BoundedDelayAccumulator,
    StragglerConfig,
    StragglerEWMA,
)
