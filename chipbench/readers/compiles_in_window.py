"""Reader ``compiles_in_window``: JAX's own compile events (a
persistent-cache load counts as one) between the window's start and end.
It has to read 0: every shape is warmed before the window."""


def read(evidence):
    return evidence.compiles_in_window
