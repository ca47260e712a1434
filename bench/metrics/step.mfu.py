"""Model FLOP utilisation of the step, in percent: the configuration's
FLOPs per step (``bench/models``, recomputation not counted) times the
steps completed per second of the window, over the chips' bf16 peak
(``bench/peaks.json``)."""


def read(run):
    if run["steps"] <= 0 or run["window_s"] <= 0:
        return None
    rate = run["flops_per_step"] * run["steps"] / run["window_s"]
    return 100.0 * rate / (run["chips"] * run["peak_flops_per_s"])
