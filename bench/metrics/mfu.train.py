"""Model FLOP/s utilisation of WSP training: forward and backward
operations per token (6 x the matmul parameters plus 3 x the causal
attention's forward, recomputation not counted; bench/flops.py) times the
tokens of the waves that landed in the traced window, over window x the
chip's peak."""
from bench import flops


def read(rec):
    t = rec.get("trace")
    if not t or not t["devices"] or rec.get("kind") != "train":
        return None
    if t["window_s"] <= 0:
        return None
    per_token = flops.train_flops_per_token(rec["cfg"], rec["traffic"]["seq"])
    tokens = rec["waves_in_window"] * rec["tokens_per_wave"]
    peak = flops.peaks(rec["device"]["kind"])["flops_per_s"]
    return 100.0 * per_token * tokens / (t["window_s"] * peak)
