"""Kernels: the Pallas ASM ReLU's share of its roofline, in percent.

For every ``asm_relu_pallas`` custom call in the served cells' compiled
programs, operations and bytes follow from its operand shapes
(``flops.asm_relu_cost``) and give a least time, the larger of
operations over the bf16 peak and bytes over the HBM bandwidth.  The
share is the least time of all its executions in the window over their
summed device time in the trace."""
from bench import flops


def read(run):
    if run.trace is None:
        return None
    least, measured, bound = 0.0, 0.0, {}
    for call in run.kernels:
        if "asm_relu_pallas" not in call["op_name"]:
            continue
        count, seconds = run.trace["ops"].get(call["name"], (0, 0.0))
        if not count:
            continue
        t, term = flops.least_time(
            *flops.asm_relu_cost(call["operands"], call["output"]),
            run.peak)
        least += t * count
        measured += seconds
        bound[term] = bound.get(term, 0.0) + t * count
    if not measured:
        return None
    run.note("asm_relu_roofline bound by "
             + max(bound, key=bound.get) + f" ({bound})")
    return 100.0 * least / measured
