"""Milliseconds of ddmin dispatch per bundle: the program's telemetry
`dispatch` spans with site=shrink (triage.py), summed over the window."""


def read(run):
    bundles = sum(1 for r in run.records if r.get("bundle"))
    spans = [s for s in run.spans
             if s.name == "dispatch" and s.labels.get("site") == "shrink"]
    return 1e3 * sum(s.dur_s for s in spans) / bundles if bundles and spans \
        else None
