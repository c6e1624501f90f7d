from paddlebox_tpu.utils.monitor import StatRegistry, stats  # noqa: F401
from paddlebox_tpu.utils.retry import (  # noqa: F401
    RetryPolicy,
    register_retryable,
    retry_call,
)
