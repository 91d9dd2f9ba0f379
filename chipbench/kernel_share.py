"""A paged grid's roofline share in a traced window (shared by the
``kernel.*_roofline`` readers), from the work the architecture counts."""
from chipbench.roofline import roofline_share


def read_share(run, kind: str, op: str):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace.op_s.get(op, 0.0)
    calls = [rows for _, k, rows in run.exec_calls if k == kind]
    if seconds <= 0.0 or not calls:
        return None
    flops = nbytes = 0.0
    for rows in calls:
        live = [r for r in rows if r.q_len > 0]
        work = run.arch.grid_work(live, run.dims, run.page, kind)
        flops += work["flops"]
        nbytes += work["bytes"]
    return roofline_share(flops, nbytes, seconds, run.peak)
