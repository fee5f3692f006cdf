"""Model FLOP/s utilization of the traced job: the model FLOPs of every
token the job emitted (reasoning and answer, each at its own context
length, ``bench/flops.py``), over the job's span, as a percentage of the
chip's bf16 peak.  Probe, proxy and prefill work is left out: it is the
cost this number exposes."""
from bench.flops import emitted_token_flops


def read(rec):
    t, job = rec.get("trace"), rec.get("traced")
    if not t or not job:
        return None
    m = rec["cfg"]["generator"]["model"]
    a = rec["mix"]["answer_len"]
    flops = sum(emitted_token_flops(m, int(P), r["n_reasoning"], a)
                for P, r in zip(job["job"]["prompt_len"], job["results"]))
    return 100.0 * flops / (t["window_s"] * rec["peaks"]["bf16_flops"])
