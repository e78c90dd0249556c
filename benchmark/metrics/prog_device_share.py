"""Share of sub-histories whose verdict the device gave, from the
program's ``check.subs{provenance=...}`` counter over the traced calls
(``telemetry.traced_counters``): every provenance but ``host-fallback``,
in %."""


def read(run):
    from jepsen_tpu import telemetry
    traced = getattr(telemetry, "traced_counters", None)
    subs = {k: v for k, v in (traced() if traced else {}).items()
            if k.startswith("check.subs{")}
    tot = sum(subs.values())
    if not tot:
        return None
    host = subs.get("check.subs{provenance=host-fallback}", 0)
    return 100.0 * (tot - host) / tot
