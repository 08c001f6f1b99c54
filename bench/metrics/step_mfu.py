"""Model step: model FLOPs of the real (unpadded) images over the device
time of the grid cells' program executions, as a share of the chip's
bf16 peak, in percent.

Executions are the trace's ``XLA Modules`` events of the served cells'
module that lie wholly in the window; each is matched to the program's
``device/device-dispatch`` span around it (host clock mapped onto the
trace's) for its count of real images.  FLOPs are those of the spatial
network the JPEG-domain one equals (the architecture module's
``model_flops``)."""


def read(run):
    if run.trace is None:
        return None
    execs = []
    for module in run.modules:
        execs += run.trace["modules"].get(module, [])
    spans = sorted((t0 + run.trace_offset, t1 + run.trace_offset, args["n"])
                   for track, name, t0, t1, args in run.spans
                   if track == "device" and name == "device-dispatch")
    images, seconds, j = 0, 0.0, 0
    for start, dur in sorted(execs):
        mid = start + dur / 2
        while j < len(spans) and spans[j][1] < mid:
            j += 1
        if j < len(spans) and spans[j][0] <= mid:
            images += spans[j][2]
            seconds += dur
    if not seconds:
        return None
    achieved = run.arch.model_flops(run.config) * images / seconds
    return 100.0 * achieved / run.peak["bf16_flops_per_s"]
