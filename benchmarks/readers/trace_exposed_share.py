"""Time in operations matching ``patterns`` (collectives) during which no
other operation runs on that device, over the traced window."""


def read(obs, spec):
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    return obs.trace.exposed_seconds(spec["patterns"]) / obs.trace.window_s * 100.0
