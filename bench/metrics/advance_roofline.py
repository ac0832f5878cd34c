"""Share of the fixpoint advance's memory roofline (``sparse/fixpoint.py``
``_chunk_loop``, ``sparse/contract.py`` ``spmm``): the least time in
which any implementation could move the bytes of the rounds the device
ran, over the device's busy time in the traced window.

One full-operator round must move at least

    nnz * (4 + v_e) + 4 * n + 4 * n * B * s_c

bytes: a 4-byte index and a ``v_e``-byte value per edge, a 4-byte row
pointer per vertex, and y and Δ each read and written once at ``s_c``
bytes a lane (1/8 for a Boolean bit, 4 for float32).  A round that skips
rows of Δ (a frontier-compacted advance) does less than this, and the
count then goes stale: a benchmark change re-derives it first.
"""


def bytes_per_round(nnz: int, n: int, b: int, edge_value_bytes: float,
                    lane_bytes: float) -> float:
    return nnz * (4 + edge_value_bytes) + 4 * n + 4 * n * b * lane_bytes


def read(run):
    t = run.trace
    if not t or not t.get("rounds") or t["busy_s"] <= 0 or not run.peaks:
        return None
    fam = run.family
    per_round = bytes_per_round(run.nnz, run.n,
                                int(run.cfg["server"]["max_batch"]),
                                fam.EDGE_VALUE_BYTES, fam.LANE_BYTES)
    least_s = t["rounds"] * per_round / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
