"""Share of the traced job's device busy time spent in the exchanges
between chips (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all`` ops), both as a mean over chips,
as a percentage (device trace).  A cell on one chip has none to read."""


def read(rec):
    t = rec.get("trace")
    if not t or t["n_devices"] < 2 or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
