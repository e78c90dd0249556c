"""Share of sub-histories the frontier-sharded route decided, over all
decided in the traced calls, from the program's ``check.routes{route=...}``
counter (``telemetry.traced_counters``), in %."""


def read(run):
    from jepsen_tpu import telemetry
    traced = getattr(telemetry, "traced_counters", None)
    routes = {k: v for k, v in (traced() if traced else {}).items()
              if k.startswith("check.routes{")}
    tot = sum(routes.values())
    if not tot:
        return None
    return 100.0 * routes.get("check.routes{route=frontier}", 0) / tot
