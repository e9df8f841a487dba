"""Share of the roofline reached by the top-k scorer: per microbatch the
least time to read H once and score its users (``costs.topk_batch``, U
the mean real users per batch), over the device time of the whole
scoring path per batch (the user-row gather and ``_topk_pallas``, whose
pad of H on every call counts against it)."""
from bench import trace as tr
from bench.metrics import costs

SCORING = r"topk|take|gather"


def read(rec):
    lo, hi = rec["window_ns"]
    devs = list(rec["trace"]["devices"].values())
    c = rec["counters"]
    if not devs or not c.get("n_batches"):
        return None
    dev_ns = tr.module_ns(devs[0], lo, hi, SCORING)
    batches = tr.module_count(devs[0], lo, hi, r"topk")
    if dev_ns <= 0 or batches == 0:
        return None
    cfg = rec["cfg"]
    f, b = costs.topk_batch(c["n_queries"] / c["n_batches"], int(cfg["n"]),
                            int(cfg["k"]))
    return 100.0 * batches * costs.least_time(f, b, rec["peaks"]) / (
        dev_ns / 1e9)
