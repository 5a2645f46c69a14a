"""Common utilities: configuration, sharding rules, pytree helpers."""

from repro.common.config import (  # noqa: F401
    ModelConfig,
    TrainConfig,
    PredictorConfig,
)
