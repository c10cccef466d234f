"""One minus the union of the device's operation intervals over the traced
window, averaged over the chips used."""


def read(obs, spec):
    if obs.trace is None:
        return None
    share = obs.trace.idle_share()
    return None if share is None else share * 100.0
