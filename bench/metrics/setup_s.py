"""Process start to the window's opening (host clock): start-up, weights
made on the device, the first load, the warm-up requests, compilation."""


def read(run):
    return run.setup_s
