"""Model FLOPs utilization of the training step: model FLOPs per token
(``flops_per_token`` of the configuration's reference, no recompute)
times the traced window's tokens per second, over the chips' bf16 peak,
in percent."""


def read(run):
    c = run.counters
    if not c.get("calls") or not c.get("window_s"):
        return None
    tokens_per_s = c["tokens_per_step"] * c["calls"] / c["window_s"]
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * c["flops_per_token"] * tokens_per_s / peak
