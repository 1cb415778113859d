"""The whole round's share of the chips' bf16 peak, in percent: the FLOPs
a round requires (``bench/counts.py``) times the rounds of the traced window,
over its seconds, the chips and the peak."""


def read(run):
    if run.peaks is None or run.window_s <= 0 or run.rounds <= 0:
        return None
    achieved = run.round_flops * run.rounds / run.window_s
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
