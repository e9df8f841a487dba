"""Share of the roofline reached by the epochs in the trace: the least
time for training ratings x epochs SGD updates (``costs.sgd_update`` at
k, float32, against ``bench/peaks.json``) over the device time of the
epoch programs (``jit_train``: the fused driver, the SPMD ring's too),
averaged over the devices.  A cut trace holds part of an epoch program
and no count of the updates in that part, so nothing is read from it."""
from bench import trace as tr
from bench.metrics import costs

EPOCH_PROGRAM = r"jit_train\b"


def read(rec):
    lo, hi = rec["window_ns"]
    devs = list(rec["trace"]["devices"].values())
    if not devs or rec["cut"]:
        return None
    dev_ns = sum(tr.module_ns(d, lo, hi, EPOCH_PROGRAM) for d in devs)
    epochs = tr.module_count(devs[0], lo, hi, EPOCH_PROGRAM)
    if dev_ns <= 0 or epochs == 0:
        return None
    c = rec["counters"]
    f, b = costs.sgd_update(int(rec["cfg"]["k"]))
    least = costs.least_time(f * c["nnz"] * epochs, b * c["nnz"] * epochs,
                             rec["peaks"])
    return 100.0 * least / (dev_ns / len(devs) / 1e9)
