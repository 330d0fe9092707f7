"""The train step's share of the chips' bf16 peak: the algorithm's FLOPs
per token (``bench/flops.py``) times the tokens of the step programs that
ran in the traced window, over the window and the chips' peak."""

from bench.flops import train_flops_per_token


def read(run):
    s = run.trace_summary
    if s is None or "train" not in run.workload:
        return None
    _, steps = s.module("stepped")
    if not steps:
        return None
    T = run.traffic["seq_len"]
    tokens = steps * run.chips * run.workload["train"]["per_chip_batch"] * T
    flops = tokens * train_flops_per_token(run.config, T)
    return 100.0 * flops / s.window_s / (run.chips * run.peaks["bf16_flops"])
