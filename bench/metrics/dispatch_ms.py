"""Grid cell: mean wall of one batch through its captured executable,
host staging, copy to the device and the read of the logits included
(the program's ``device/device-dispatch`` spans that start in the
window), in milliseconds."""


def read(run):
    walls = [t1 - t0 for track, name, t0, t1, _ in run.spans
             if track == "device" and name == "device-dispatch"
             and run.window[0] <= t0 < run.window[1]]
    return sum(walls) / len(walls) * 1e3 if walls else None
