"""Roofline share of the paged decode kernel (kernels/flash_decode.py,
flash_decode_paged) over the traced window: the least time the chip could
take for the work the decode steps needed (the larger of operations over
peak FLOP/s and bytes over peak HBM bandwidth, per layer call, from each
step's live rows and their cached lengths; bench/flops.py), over the
device time of the kernel's events. It is bound by memory: the bytes
term is the larger one at every decode shape of these cells."""
from bench import flops, trace_reduce

# the kernel is the only custom call of the decode step, and carries its name
KERNEL = "dec_fn"


def read(rec):
    t, w = rec.get("trace"), rec.get("window")
    if not t or not t["devices"] or not w or not rec.get("page_pool"):
        return None
    spent = trace_reduce.kernel_seconds(t, KERNEL)
    if spent <= 0:
        return None
    cfg = rec["cfg"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    p = flops.peaks(rec["device"]["kind"])
    least = 0.0
    for _, _, _, lens in w["decode_calls"]:
        f, b = flops.flash_decode_paged(lens, H, KV, hd, rec["width"])
        least += max(f / p["flops_per_s"], b / p["hbm_bytes_per_s"])
    return 100.0 * least * cfg["num_hidden_layers"] / spent
