"""The mean of the window's ``samples[of]``, over all of them."""


def read(reading, of: str):
    values = reading.outcome["samples"].get(of)
    return sum(values) / len(values) if values else None
