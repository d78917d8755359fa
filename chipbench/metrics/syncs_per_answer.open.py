"""`syncs_per_answer` in an open-loop cell, where it moves `answer_p50_ms.open`."""

from chipbench.metrics.syncs_per_answer import read  # noqa: F401
