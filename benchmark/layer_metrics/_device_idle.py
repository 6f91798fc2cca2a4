"""1 - (union of the device's operations) / (traced window). With half
the published depth the host's share, and so this, is larger than in a
deployment."""


def read(run):
    trace = run["trace"]
    if not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
