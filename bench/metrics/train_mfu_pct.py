"""Model FLOP utilization of the whole train step: model operations per
token (``flops.decoder_train_flops_per_token``) times the tokens trained in
the window, over the window's seconds, the chips and the chip's bf16 peak.
Where trial set-up is inside the window (``train_mfu_pct.trials``), its
compilation counts as time."""


def read(r):
    h = r.host
    if not h.get("tokens"):
        return None
    return 100.0 * h["flops_per_token"] * h["tokens"] / (h["window_s"] * h["chips"] * r.peaks["bf16_flops"])
