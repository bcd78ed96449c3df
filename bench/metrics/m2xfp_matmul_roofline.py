"""The serve-GEMM kernel's share of its roofline: over every kernel call
of the traced launches, the least time each call could take (its FLOPs at
peak, or its packed weight, bfloat16 inputs and float32 outputs at peak
HBM bandwidth) over the kernel's device time. A launch feeds the kernel B
rows (decode) or B * chunk rows (prefill); every layer calls the seven
GEMM sites once."""


def read(run):
    t = run.trace
    if not t or not t["kernel_ns"] or not t["launches"]:
        return None
    sites = run.counts.gemm_sites(run.model)
    n_layers = run.model["num_hidden_layers"]
    if t["kernel_calls"] != len(t["launches"]) * len(sites) * n_layers:
        return None                    # calls not all seen: no share
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    b, chunk = run.deployment["n_slots"], run.deployment["prefill_chunk"]
    ideal = 0.0
    for name, _ in t["launches"]:
        rows = b if name.startswith("jit_decode_fn") else b * chunk
        ideal += n_layers * sum(
            run.counts.gemm_call_ideal_s(rows, k, n, run.bits, pf, pb)
            for _, k, n in sites)
    return 100.0 * ideal / (t["kernel_ns"] / 1e9)
