"""Model FLOPs of the window's decode steps over their decode walls and the
chip's bf16 peak."""
from bench.flops import decode_step_flops


def read(run):
    t = run.tpot_s()
    if not t:
        return None
    S, G = run.traffic["prompt_len"], run.traffic["gen_tokens"]
    # step j of a request attends over S + j + 1 tokens
    per_req = sum(decode_step_flops(run.config, S + j + 1, run.arch)
                  for j in range(G))
    return 100.0 * per_req / (G * t) / run.peak.bf16_flops
