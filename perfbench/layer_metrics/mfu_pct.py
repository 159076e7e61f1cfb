"""Model FLOP/s utilisation of the train step (%): the FLOPs a trained token
requires (``peaks.train_flops_per_token``, recomputation not counted) times
tokens per second of the window, over chips times the bf16 peak."""
from perfbench import peaks


def read(run):
    if "flops_per_token" not in run or run["device"]["platform"] != "tpu":
        return None   # a CPU rehearsal has no peak to stand against
    peak = peaks.peaks_for(run["device_kind"])["flops_bf16"] * run["chips"]
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / peak
