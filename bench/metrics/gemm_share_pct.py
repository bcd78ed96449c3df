"""Serve-GEMM kernel device time over launch device time in the traced
stretch: whether the kernel or the rest of the step (attention, the
KV-pool copy, ``lm_head``) sets the pace."""


def read(run):
    t = run.trace
    if not t or not t["launch_ns"] or not t["kernel_ns"]:
        return None
    return 100.0 * t["kernel_ns"] / t["launch_ns"]
