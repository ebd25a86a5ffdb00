"""Shared utilities: env config, logging setup, cleanup hooks."""

from predictionio_tpu.utils.config import pio_env_vars, pio_home
from predictionio_tpu.utils.logging_util import configure_logging
from predictionio_tpu.utils import cleanup

__all__ = ["pio_env_vars", "pio_home", "configure_logging", "cleanup"]
