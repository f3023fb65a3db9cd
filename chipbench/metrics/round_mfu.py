"""round_mfu: the whole round's share of the chip's bf16 peak, in
percent: the algorithm's FLOPs per round (`flops.round_flops`: local
training 3 x forward, the refresh's 4 probes per candidate, the Eq.-4
mix, the validation forward; averaged over the traced rounds) times the
rounds per second of the traced window, over the peak of `peaks.json`."""


def read(run):
    if not run.get("round_flops") or not run.get("window_s"):
        return None
    rate = run["traced_rounds"] / run["window_s"]
    return 100.0 * run["round_flops"] * rate / \
        run["peak"]["bf16_flops_per_s"]
