"""The whole step's share of the chip's binding peak over the traced
stretch: the least time the required work of every traced launch could
take (its FLOPs at peak FLOP/s or its bytes at peak HBM bandwidth,
whichever is longer), over the stretch's seconds. Required work comes from
``bench/counts/<family>.py``, from shapes."""


def read(run):
    t = run.trace
    if not t or not run.steps or t["window_ns"] <= 0:
        return None
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    ideal = 0.0
    for s in run.steps:
        flops, nbytes = run.counts.launch_work(
            run.model, s.fed, s.pos, s.generated, run.bits)
        ideal += max(flops / pf, nbytes / pb)
    return 100.0 * ideal / (t["window_ns"] / 1e9)
