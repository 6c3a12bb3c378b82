"""The paged-attention kernel's share of its roofline: the least time the
chip needs for the kernel's calls in the traced window (each the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth) over their summed
device time.  At batch 1 the bytes bound it."""
from bench.flops import least_seconds, paged_attn_bytes, paged_attn_flops


def read(run):
    tr = run.trace
    if tr is None or not tr.kernel_s:
        return None
    c = run.config
    S, G, L = (run.traffic["prompt_len"], run.traffic["gen_tokens"],
               c["num_hidden_layers"])
    least = L * sum(least_seconds(paged_attn_flops(c, S + j + 1),
                                  paged_attn_bytes(c, S + j + 1),
                                  run.peak)[0] for j in range(G))
    return 100.0 * least * tr.requests_traced / tr.kernel_s
