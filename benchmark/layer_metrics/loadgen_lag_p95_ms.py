"""How late the generator sent, against when each request fell due."""


def read(run):
    lag = run["raw"].get("lag_s")
    if not lag:
        return None
    lag = sorted(lag)
    return 1e3 * lag[max(0, -(-95 * len(lag) // 100) - 1)]
