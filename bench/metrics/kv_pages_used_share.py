"""Peak pages the traced job's generator page pool held at once, over the
pool's data pages (``gen.peak_pages`` / ``gen.pool_pages``), as a
percentage: how much of the reserved KV pool a job touches.  Read from the
counters the program hands back on its results (``ServeResults.telemetry``);
None where the program keeps none."""


def read(rec):
    tel = getattr((rec.get("traced") or {}).get("results"), "telemetry",
                  None)
    if tel is None:
        return None
    c = tel.summary()["counters"]
    if not c.get("gen.pool_pages"):
        return None
    return 100.0 * c["gen.peak_pages"] / c["gen.pool_pages"]
