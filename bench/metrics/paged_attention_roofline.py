"""``paged_attention`` kernel time against its roofline in the traced job.

The least time is the larger of the attention FLOPs over the bf16 peak and
the K/V bytes over the HBM bandwidth, for the calls the served requests
needed (``bench/flops.py``): the generator's decode steps and forced
answers, the probe on the monitor model, and in a proxy cell the proxy's
shadow decode.  The peaks are those of every chip the trace holds, and
the time the summed device time of the ``paged_attention`` events, mean
over chips.  The bound is memory at these shapes."""
from bench.flops import paged_attention_work, roofline_share


def read(rec):
    t, job = rec.get("trace"), rec.get("traced")
    if not t or not job or not t["kernel_s"].get("paged_attention"):
        return None
    cfg, mix = rec["cfg"], rec["mix"]
    proxy = cfg["monitor"] == "proxy"
    every, a = mix["eval_every"], mix["answer_len"]
    flops = nbytes = 0.0
    for P, r in zip(job["job"]["prompt_len"], job["results"]):
        n = r["n_reasoning"]
        f, b = paged_attention_work(cfg["generator"]["model"], int(P), n,
                                    every, a, probe=not proxy)
        flops, nbytes = flops + f, nbytes + b
        if proxy:
            f, b = paged_attention_work(cfg["proxy"]["model"], int(P), n,
                                        every, a, answer=False)
            flops, nbytes = flops + f, nbytes + b
    share, _ = roofline_share(flops, nbytes, t["kernel_s"]["paged_attention"],
                              rec["peaks"], t["n_devices"])
    return 100.0 * share
