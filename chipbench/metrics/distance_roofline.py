"""The batched distance kernel's share of its roofline, in percent.

Work per call: read the (V_Z, V_X) counts and the (Q, V_X) targets, write
the (Q, V_Z) distances, all float32, with Q the server's query slots.
Its operations (a few per counts entry and target) are negligible
against those bytes, so bandwidth bounds it.
"""

from chipbench.roofline import share_pct

KERNEL = "distance_multi"


def work_bytes(calls: int, v_z: int, v_x: int, q: int) -> float:
    return calls * 4.0 * (v_z * v_x + q * v_x + q * v_z)


def read(run):
    seconds, calls = run.trace.op_time(KERNEL)
    if not calls:
        return None
    cfg = run.config
    q = run.mix["server"]["max_queries"]
    return share_pct(
        device_kind=run.device_kind, seconds=seconds,
        bytes_moved=work_bytes(calls, cfg["v_z"], cfg["v_x"], q),
    )
