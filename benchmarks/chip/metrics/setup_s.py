"""``setup_s``: process start until the measured window opens (weights,
artifact, engine or step, compilation or cache loads, warm load)."""


def read(rec):
    return rec["setup_s"]
