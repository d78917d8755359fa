"""`distance_roofline` in an open-loop cell, where it moves `answer_p50_ms.open`."""

from chipbench.metrics.distance_roofline import read  # noqa: F401
