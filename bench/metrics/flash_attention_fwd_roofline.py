"""Roofline share of the prefill attention kernel (kernels/
flash_attention.py, flash_attention_fwd) over the traced window: the least
time for the causal attention of the real prompt rows of each prefill
group (operations over peak FLOP/s, or bytes over HBM bandwidth where that
is larger, per layer call; bench/flops.py), over the device time of the
kernel's events. It is bound by compute at these prompt lengths. The
kernel runs every row of the compiled [max_batch, prompt_len] shape, so
padding shows here as a low share."""
from bench import flops, trace_reduce

# the kernel is the only custom call of the prefill step, and carries its name
KERNEL = "pre_fn"


def read(rec):
    t, w = rec.get("trace"), rec.get("window")
    if not t or not t["devices"] or not w or not rec.get("page_pool"):
        return None
    spent = trace_reduce.kernel_seconds(t, KERNEL)
    if spent <= 0 or not w["prefill_calls"]:
        return None
    cfg = rec["cfg"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    p = flops.peaks(rec["device"]["kind"])
    least = 0.0
    for _, _, _, lens in w["prefill_calls"]:
        f, b = flops.flash_attention(lens, H, KV, hd, rec["width"])
        least += max(f / p["flops_per_s"], b / p["hbm_bytes_per_s"])
    return 100.0 * least * cfg["num_hidden_layers"] / spent
