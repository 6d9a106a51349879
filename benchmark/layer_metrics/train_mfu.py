"""Model FLOP/s utilisation of the train step: tokens/s x FLOPs the
algorithm needs per token / (chips x the chip's bf16 peak). The FLOP
function and the peak table are the benchmark's (lib/flops.py,
lib/peaks.py); an unknown device_kind is an error."""
from benchmark.lib.flops import train_flops_per_token
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("train step", "%", "host_clock",
                              "train_tokens_per_s_chip")


def read(obs):
    if "tokens" not in obs:
        return None
    per_token = train_flops_per_token(obs["model_class"],
                                      obs["model_kwargs"], obs["seq_len"])
    achieved = obs["tokens"] / obs["window_s"] * per_token
    return 100.0 * achieved / (
        obs["chips"] * peak(obs["device_kind"], "bf16_flops_per_s"))
