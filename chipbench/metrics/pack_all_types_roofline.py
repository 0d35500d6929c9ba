"""Share of the jitted pack ``_pack_all_types``'s device time that the chip's
roofline says the packing problems need at the least (%).

The operations and bytes are counted from the problem, not from the
implementation, so no later rewrite (replicated fills, no class collapse)
can push the share past 100%.  Per pack problem of T tasks, K types, F
families, R resources and W workloads it reads the task rows (F*R demands,
workload, RP and job RP: T*(F*R + 3) values), the catalog (K*R) and the
pairwise matrix (W*W), and writes one placement per task (T), all 4-byte
values; it tests each task against each type (T*K*R operations).  An
overflow retry of one call (the same T at twice the fill buffer) is the same
problem and counts once.  The least time is the larger of operations over
the peak rate and bytes over the peak bandwidth.
"""


def problems(spans):
    """Task counts of the distinct pack problems among the spans."""
    out, prev = [], None
    for s in spans:
        retry = (prev is not None and s["n_tasks"] == prev["n_tasks"]
                 and s["max_fills"] == 2 * prev["max_fills"])
        if not retry:
            out.append(s["n_tasks"])
        prev = s
    return out


def least_time_s(n_tasks, config, peaks):
    K = len(config["catalog"])
    F = len(config["families"])
    R = len(config["catalog"][0]["capacity"])
    W = config["n_workloads"]
    values = n_tasks * (F * R + 3) + K * R + W * W + n_tasks
    ops = n_tasks * K * R
    return max(4.0 * values / peaks["hbm_bytes_per_s"],
               ops / peaks["flops_per_s"])


def read(rec):
    tr, spans, peaks = rec["trace"], rec["pack_spans"], rec["peaks"]
    if tr is None or not spans or peaks is None or tr["kernel_s"] <= 0:
        return None
    least = sum(least_time_s(t, rec["config"], peaks)
                for t in problems(spans))
    return 100.0 * least / tr["kernel_s"]
