"""Executables built (compiled or fetched) after the window opened. Anything
but 0 is a warm-up fault."""


def read(obs, spec):
    return float(len(obs.builds_in_window))
