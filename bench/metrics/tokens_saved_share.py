"""Reasoning tokens EAT saved in the traced job against a fixed budget:
1 - sum(n_reasoning) / (requests x budget), as a percentage (a count the
program's results give)."""


def read(rec):
    job = rec.get("traced")
    if not job:
        return None
    res = job["results"]
    used = sum(r["n_reasoning"] for r in res)
    return 100.0 * (1.0 - used / (len(res) * rec["mix"]["budget"]))
