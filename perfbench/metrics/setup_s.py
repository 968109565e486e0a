"""Process start to the first timed request, compilation included."""


def read(rec):
    return rec.setup_s
