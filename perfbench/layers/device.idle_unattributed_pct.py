"""Share of the traced window in which the device was idle while the
reduction found no host cause: no program stage span and no Python frame
other than a wait open on any host thread it reads ("host waiting")."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * t["idle_by_cause"].get("host waiting", 0.0) / t["window_s"]
