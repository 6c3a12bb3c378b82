"""`jit.trace` spans in the window per `serve` span: how many functions JAX
traced anew for each request, from the program's spans of a traced run."""


def read(run):
    spans = getattr(run, "spans", None) or ()
    served = sum(1 for e in spans if e.name == "serve")
    if not served:
        return None
    return sum(1 for e in spans if e.name == "jit.trace") / served
