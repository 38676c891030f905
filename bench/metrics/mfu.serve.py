"""Model FLOP/s utilisation of serving: the model's operations for the
real (unpadded) prompt tokens prefilled and the tokens decoded in the
traced window, over window x the chip's peak (bench/flops.py,
bench/peaks.json). Prefill counts every prompt position's projections
and causal attention and one output-head product per request (the program
computes logits for the last position only); a decode token counts every
projection, the head and attention over its row's cached positions.
Padded rows and positions are not counted."""
from bench import flops


def read(rec):
    t, w = rec.get("trace"), rec.get("window")
    if not t or not t["devices"] or not w or t["window_s"] <= 0:
        return None
    cfg = rec["cfg"]
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    body = 2.0 * flops.matmul_params(cfg) - head
    total = 0.0
    for _, _, _, lens in w["prefill_calls"]:
        for s in lens:
            s = int(s)
            total += body * s + head
            total += sum(flops.attention_flops(cfg, c + 1) for c in range(s))
    for _, _, _, lens in w["decode_calls"]:
        for n in lens:
            total += body + head + flops.attention_flops(cfg, int(n))
    peak = flops.peaks(rec["device"]["kind"])["flops_per_s"]
    return 100.0 * total / (t["window_s"] * peak)
