"""Model FLOP/s utilization of the whole train step: FLOPs the forward and
backward passes require per token (``benchlib.flops``; recompute not
counted) times tokens per second, over chips times the bf16 peak."""
from benchlib.flops import train_flops_per_token


def read(run):
    flops = train_flops_per_token(run.model, run.seq) * run.tokens_per_s
    return flops / (run.chips * run.peaks.bf16_flops) * 100.0
