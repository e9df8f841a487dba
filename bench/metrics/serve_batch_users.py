"""Users per microbatch the server scored in the window:
``RecServer.n_queries / n_batches``, the program's own counters."""


def read(rec):
    c = rec["counters"]
    if not c.get("n_batches"):
        return None
    return c["n_queries"] / c["n_batches"]
