"""A stage of the run's set-up by the harness's own clock
(``harness/e2e.py::setup_clock``), in seconds: what the interpreter and the
machine took before ``setup_s`` starts, kept beside it."""


def read(obs, spec):
    return obs.setup.get(spec["stage"])
