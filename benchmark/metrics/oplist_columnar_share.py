"""Share of the KV-valued Op-list histories the checker strained by key
inside its keyed conversion walk, not as per-key Op lists, from the
program's ``partition.oplist{route=...}`` counter over the traced calls
(``telemetry.traced_counters``), in %."""


def read(run):
    from jepsen_tpu import telemetry
    traced = getattr(telemetry, "traced_counters", None)
    routes = {k: v for k, v in (traced() if traced else {}).items()
              if k.startswith("partition.oplist{")}
    tot = sum(routes.values())
    if not tot:
        return None
    return 100.0 * routes.get("partition.oplist{route=columnar}", 0) / tot
