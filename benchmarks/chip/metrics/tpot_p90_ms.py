"""90th percentile of time per output token, (last - first) / (tokens - 1),
over requests due in the window whose output spans two decode chunks or
more. A failed request counts as missing."""
from benchmarks.chip.record import percentile, tpot_s


def read(run):
    vals = [v for v in (tpot_s(r) for r in run.due_in_window())
            if v is not None]
    if run.kind != "serve" or not vals:
        return None
    return 1e3 * percentile(vals, 90)
