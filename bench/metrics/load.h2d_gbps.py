"""Host-to-device rate of set-up's first load: bytes the chunked pipeline
moved over its wall (the engine's DataLoadStats)."""


def read(run):
    st = run.first_load
    if not st["bytes_h2d"] or st["transfer_seconds"] <= 0:
        return None
    return st["bytes_h2d"] / st["transfer_seconds"] / 1e9
