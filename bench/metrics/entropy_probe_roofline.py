"""``entropy_probe`` kernel time against its roofline in the traced job.

Every call must read the monitor model's unembedding once (d x padded
vocab, bf16); every EAT evaluation a request consumed needs one row of
dot products over it.  The least time (memory-bound at these shapes) over
the summed device time of the ``entropy_probe`` events.  The monitor is
the generator in a self-EAT cell and the proxy in a proxy cell."""
from bench.flops import entropy_probe_work, roofline_share


def read(rec):
    t, job = rec.get("trace"), rec.get("traced")
    if not t or not job or not t["kernel_s"].get("entropy_probe"):
        return None
    cfg, every = rec["cfg"], rec["mix"]["eval_every"]
    m = cfg["proxy" if cfg["monitor"] == "proxy" else "generator"]["model"]
    calls = t["kernel_calls"]["entropy_probe"]
    evals = sum((r["n_reasoning"] - 1) // every for r in job["results"])
    flops = entropy_probe_work(m, evals)[0]
    nbytes = calls * entropy_probe_work(m, 1)[1]
    share, _ = roofline_share(flops, nbytes, t["kernel_s"]["entropy_probe"],
                              rec["peaks"])
    return 100.0 * share
