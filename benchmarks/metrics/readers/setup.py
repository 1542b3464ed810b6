"""Process start to the opening of the measured window, by the host's
clock: imports, weights, compilation or cache reads, the correctness probe,
warm-up and (serve cells) the traffic that runs before the window."""


def read(reading):
    return reading.outcome["setup_s"]
