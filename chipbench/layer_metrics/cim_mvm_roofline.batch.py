"""Share of the roofline reached by the Pallas kernel ``cim_mvm``,
percent: the least time of its calls over their summed device time.

Each call's least time is max(ops / peak FLOP/s, bytes / peak HBM
bytes/s), with ops and bytes from the call's shapes alone
(``chipbench.counts``), read from the op's HLO text in the trace:
result (M, N) and activations (M, I).  The Pallas path carries no gain.
"""
from chipbench import counts, trace


def read(run):
    if run.trace is None or not run.peak:
        return None
    ops = trace.kernel_ops(run.trace, "cim_mvm")
    cim = run.cfg["cim"]
    least = busy = 0.0
    bound = {"compute": 0.0, "memory": 0.0}
    for op in ops:
        shapes = trace.operand_shapes(op)
        if len(shapes) < 2:
            return None
        (m, n), (_, i) = shapes[0][1][-2:], shapes[1][1][-2:]
        t, side = counts.least_seconds(
            counts.cim_mvm_flops(m, i, n),
            counts.cim_mvm_bytes(m, i, n, n_bits=cim["n_bits"],
                                 rows=cim["rows"], cols=cim["cols"],
                                 gain=False),
            run.peak)
        least += t
        bound[side] += t
        busy += (op.end - op.start) * 1e-9
    if busy <= 0:
        return None
    run.notes["cim_mvm_bound"] = max(bound, key=bound.get)
    return 100.0 * least / busy
