"""Host seconds of the program's packing (``MCProblem.packed`` ->
``core/partition.py`` ``pack``), from the benchmark's own span around
the call."""


def read(rec):
    return rec["spans"].get("pack")
