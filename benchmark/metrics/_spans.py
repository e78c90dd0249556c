"""Shared arithmetic of the span readers."""


def bench_ms_per_khist(run, name):
    """Summed duration of the benchmark's ``name`` spans in the traced
    window, in ms per thousand histories checked; None without spans."""
    rec = run.recorder
    if rec is None or run.khist <= 0:
        return None
    ds = [t1 - t0 for n, t0, t1 in rec.spans if n == name]
    return sum(ds) * 1e3 / run.khist if ds else None


def program_self_ms_per_khist(run, name):
    """Self time of the program's own ``name`` spans (telemetry records,
    microseconds): each span's duration less its child spans', in ms
    per thousand histories; None where the program wrote none."""
    rec = run.recorder
    spans = getattr(rec, "program_spans", None) if rec else None
    if not spans or run.khist <= 0:
        return None
    mine = {s["id"]: s["dur"] for s in spans
            if s.get("ph") == "X" and s.get("name") == name}
    if not mine:
        return None
    child = sum(s["dur"] for s in spans
                if s.get("ph") == "X" and s.get("parent") in mine)
    return (sum(mine.values()) - child) / 1e3 / run.khist
