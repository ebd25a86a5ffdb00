"""Environment configuration helpers.

Parity with WorkflowUtils.pioEnvVars (core/.../workflow/WorkflowUtils.scala:193)
and the conf/pio-env.sh contract: PIO_* variables configure storage topology
(see storage/registry.py) and runtime homes.
"""

from __future__ import annotations

import os
from typing import Dict


def pio_home() -> str:
    return os.environ.get(
        "PIO_HOME", os.path.join(os.path.expanduser("~"), ".pio_tpu"))


def pio_env_vars() -> Dict[str, str]:
    """All PIO_* env vars (passed between processes like Runner.scala:216)."""
    return {k: v for k, v in os.environ.items() if k.startswith("PIO_")}
