"""Time per output token: first token to last, summed over the window's
requests, over their decode steps (the benchmark's own clock; the last
token is on the host when the program's `generate` returns)."""


def read(run):
    t = run.tpot_s()
    return None if t is None else 1e3 * t
