"""``serve.run`` call -> the replica answers its first call."""


def read(run):
    return run["raw"].get("ready_s")
