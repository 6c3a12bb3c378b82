"""Mean init + load + profile of the window's cold starts: a model scaled
to zero coming back from the Reuse Store's retained tensors."""


def read(run):
    cold = [s.rec for s in run.served if s.rec.cold]
    if not cold:
        return None
    return 1e3 * sum(r.init_s + r.load_s + r.profile_s for r in cold) / len(cold)
