"""Share of sub-histories whose verdict a device kernel gave (the
program's provenance: no mark, ``device`` or ``wgl-dc``), not the host
or native engines (``host-fallback``), in %."""


def read(run):
    rec = run.recorder
    if rec is None or not rec.subs:
        return None
    tot = sum(rec.subs.values())
    return 100.0 * (tot - rec.subs.get("host-fallback", 0)) / tot
