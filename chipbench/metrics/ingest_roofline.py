"""The ingest histogram kernel's share of its roofline, in percent.

Work is what the algorithm needs, not what the kernel's tiles, padding or
one-hots touch: every tuple ingested is read once as an int32 (z, x)
pair, and each call writes the (V_Z, V_X) counts delta and the (V_Z,)
row sums in float32. Its operations (one add per tuple) are negligible
against those bytes, so bandwidth bounds it.
"""

from chipbench.roofline import share_pct

KERNEL = "histogram_with_rowsums"


def work_bytes(tuples: int, calls: int, v_z: int, v_x: int) -> float:
    return 8.0 * tuples + calls * 4.0 * v_z * (v_x + 1)


def read(run):
    seconds, calls = run.trace.op_time(KERNEL)
    if not calls:
        return None
    cfg = run.config
    return share_pct(
        device_kind=run.device_kind, seconds=seconds,
        bytes_moved=work_bytes(run.counters["tuples_read"], calls, cfg["v_z"], cfg["v_x"]),
    )
