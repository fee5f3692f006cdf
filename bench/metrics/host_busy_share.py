"""Share of the traced job's ``serve`` span in which the serve loop's host
code was not blocked on a device->host read: (``serve`` - the summed
``serve.wait`` spans) / ``serve``, as a percentage.  Read from the spans
the program hands back on its results (``ServeResults.telemetry``); None
where the program keeps none."""


def read(rec):
    tel = getattr((rec.get("traced") or {}).get("results"), "telemetry",
                  None)
    if tel is None:
        return None
    spans = tel.summary()["spans"]
    if "serve" not in spans:
        return None
    serve = spans["serve"]["total_s"]
    wait = spans.get("serve.wait", {}).get("total_s", 0.0)
    return 100.0 * (serve - wait) / serve
