"""Useful updates over attempted ones in the fused stream the program
built: training ratings / (slots x p).  Padding slots are updates the
stream attempts and masks out."""


def read(rec):
    c = rec["counters"]
    if not c.get("slots"):
        return None
    return c["nnz"] / (c["slots"] * c["p"])
