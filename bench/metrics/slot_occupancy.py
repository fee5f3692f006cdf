"""Share of the traced job's slot-steps that advanced a request: the
finished requests' decode steps, sum(n_reasoning - 1) (``row_steps``), over
the batch-wide steps times the slots (``decode_steps`` x ``slots``), as a
percentage.  Read from the counters the program hands back on its results
(``ServeResults.telemetry``); None where the program keeps none."""


def read(rec):
    tel = getattr((rec.get("traced") or {}).get("results"), "telemetry",
                  None)
    if tel is None:
        return None
    c = tel.summary()["counters"]
    if not c.get("decode_steps") or not c.get("slots"):
        return None
    return 100.0 * c["row_steps"] / (c["decode_steps"] * c["slots"])
