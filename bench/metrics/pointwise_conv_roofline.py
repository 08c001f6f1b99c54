"""Kernels: the Pallas pointwise convolution's share of its roofline, in
percent.

For every ``pointwise_conv_pallas`` custom call in the served cells'
compiled programs (the 1x1 stride-1 convolutions as a channel GEMM per
coefficient, ``kernels/pointwise_conv.py``), operations and bytes follow
from its operand shapes (``cost`` below) and give a least time, the
larger of operations over the bf16 peak and bytes over the HBM
bandwidth.  The share is the least time of all its executions in the
window over their summed device time in the trace.  A program with no
such kernel reads nothing."""
from bench import flops


def cost(operands: list[str], output: str) -> tuple[float, float]:
    """Operands ``(M, C, w)`` activation, ``(O, C)`` weights, ``(O, 1)``
    DC shift; output ``(M, O, w)``.  FLOPs ``2·M·O·C·w``; bytes: each
    operand and the output moved through HBM once (an array the program
    placed in VMEM costs none)."""
    _dtype, (m, c, w) = flops.parse_shape(operands[0])
    _dtype, (o, _c) = flops.parse_shape(operands[1])
    nbytes = flops._hbm_bytes(output) + sum(flops._hbm_bytes(x)
                                             for x in operands)
    return 2.0 * m * o * c * w, float(nbytes)


def read(run):
    if run.trace is None:
        return None
    least, measured, bound = 0.0, 0.0, {}
    for call in run.kernels:
        if "pointwise_conv_pallas" not in call["op_name"]:
            continue
        count, seconds = run.trace["ops"].get(call["name"], (0, 0.0))
        if not count:
            continue
        t, term = flops.least_time(*cost(call["operands"], call["output"]),
                                   run.peak)
        least += t * count
        measured += seconds
        bound[term] = bound.get(term, 0.0) + t * count
    if not measured:
        return None
    run.note("pointwise_conv_roofline bound by "
             + max(bound, key=bound.get) + f" ({bound})")
    return 100.0 * least / measured
