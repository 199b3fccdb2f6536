"""Useful lane-steps over paid lane-steps: run_batch's own count
(`summary["occupancy"]`), averaged over the window's calls (each call
sweeps the same number of seeds)."""


def read(run):
    occ = [r["occupancy"] for r in run.records]
    return 100.0 * sum(occ) / len(occ) if occ else None
