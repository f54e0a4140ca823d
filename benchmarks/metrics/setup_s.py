"""Process start to the first step of the window: reach the chip, build the
lane, draw from the seed, compile or load from the cache, the compared steps."""


def read(record):
    return record["setup_s"]
