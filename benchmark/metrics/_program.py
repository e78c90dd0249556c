"""Shared arithmetic of the readers of the program's own spans over the
traced calls (``run.recorder.program_spans``, microsecond records)."""


def program_spans(run) -> list:
    """The program's completed spans, oldest first; [] without any."""
    rec = run.recorder
    return [s for s in (getattr(rec, "program_spans", None) or [])
            if s.get("ph") == "X"] if rec else []


def total_ms_per_khist(run, name):
    """Summed duration of the program's ``name`` spans, in ms per
    thousand histories; None where the program wrote none."""
    ds = [s["dur"] for s in program_spans(run) if s["name"] == name]
    if not ds or run.khist <= 0:
        return None
    return sum(ds) / 1e3 / run.khist

