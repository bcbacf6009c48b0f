from repro.serving.engine import (  # noqa: F401
    Request,
    ServingEngine,
    freeze_params,
    init_packed_params,
    packed_fraction,
)
from repro.serving.kv_cache import PagedKVCache  # noqa: F401
from repro.serving.prefix_cache import PrefixCache  # noqa: F401
from repro.serving.scheduler import ChunkedScheduler, SlotState, StepPlan  # noqa: F401
