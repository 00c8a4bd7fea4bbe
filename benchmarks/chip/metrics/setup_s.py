"""Process start to window open: imports, weights made from the seed,
quantization and its lane-safety check, compile-cache reads, warm-up."""


def value(rec):
    return rec["setup_s"]
