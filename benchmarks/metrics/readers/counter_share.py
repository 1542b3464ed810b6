"""A share of exact counts made by the program over the window:
``100 * counters[part] / product(counters[of])``."""


def read(reading, part: str, of):
    counters = reading.outcome["counters"]
    whole = 1.0
    for name in of:
        whole *= counters[name]
    return 100.0 * counters[part] / whole if whole else None
