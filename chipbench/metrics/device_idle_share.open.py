"""`device_idle_share` in an open-loop cell, where it moves `answer_p50_ms.open`."""

from chipbench.metrics.device_idle_share import read  # noqa: F401
