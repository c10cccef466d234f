"""A statistic of the client's own stamps (``harness/e2e.py::summarize``)
that a cell keeps as a per-layer metric because it is too unsteady there to
carry a bound."""


def read(obs, spec):
    return obs.client.get(spec["stat"])
