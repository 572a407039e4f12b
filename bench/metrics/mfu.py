"""Model FLOP/s utilization of the whole step: the FLOPs the served
decisions require (bench/work.py) per second of the traced window, over
the chips' bf16 peak."""


def read(run):
    if run.requests == 0:
        return None
    return 100.0 * run.required_flops() / run.seconds / run.peak_flops()
