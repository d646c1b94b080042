"""setup_s: seconds from process start to the first timed round: imports,
the compile cache, the device check, the engine, the tables loaded from
the seed, and the set-up rounds that compile and warm every program."""


def read(rec):
    return rec.setup_s
