"""`round_device_ms` in an open-loop cell, where it moves `answer_p50_ms.open`."""

from chipbench.metrics.round_device_ms import read  # noqa: F401
