"""Device idle share of the traced job: 1 minus the union of device-op
intervals over the job's span, as a percentage (device trace)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
