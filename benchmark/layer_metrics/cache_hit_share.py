"""Share of the last census that the device-resident row cache served
(the gauge ``cache.hit_rate`` as begin_pass set it), in %."""


def read(run):
    v = run.after["gauges"].get("cache.hit_rate")
    return None if v is None else 100.0 * v
