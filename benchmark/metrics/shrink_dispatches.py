"""ddmin dispatches per bundle: the count of the program's telemetry
`dispatch` spans with site=shrink (triage.py) over the window."""


def read(run):
    bundles = sum(1 for r in run.records if r.get("bundle"))
    n = sum(1 for s in run.spans
            if s.name == "dispatch" and s.labels.get("site") == "shrink")
    return n / bundles if bundles and n else None
